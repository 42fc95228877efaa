"""Wall-time attribution tracing — the host-side third of observability.

:mod:`apex_tpu.pyprof` answers *where device time went*;
:mod:`apex_tpu.monitor` answers *is the run healthy over time*.  What
neither could answer is where the **wall** time goes when it is not on
the device — ROADMAP item 2's 84 TF/s-device / 33 TF/s-wall gap was a
single opaque number.  This module is the instrument for that surgery,
in four pieces:

* :func:`span` — the program's ONE host-span primitive, called where
  the work happens (``with span("apex.serve.decode.fetch"):``).  It
  always opens a ``jax.profiler.TraceAnnotation``: inert unless a
  profiler session is on, and then an event of the same ``.xplane.pb``
  as the device plane, so a host phase and the device's ops share one
  clock and an idle gap of the device can be put down to what the host
  was doing.  Names are ``apex.<layer>.<phase>``
  (docs/api/observability.md has the table).  :func:`watch_collector`
  adds one span the program does not open itself: ``apex.host.gc``,
  round each of Python's garbage collections.
* :class:`SpanTracer` — what :func:`span` also records into when one
  is installed (:func:`set_tracer`, :class:`TraceSession`):
  thread-and-process-aware monotonic timing on the host's own clock.
  Spans drain as ``span`` events into the existing crash-safe JSONL
  sinks and export as Chrome trace-event JSON
  (:meth:`SpanTracer.chrome_trace`), so host spans load into Perfetto
  side-by-side with ``jax.profiler`` device traces — the TPU-native
  form of the reference's nvtx→nvvp join
  (ref: apex/pyprof/nvtx/nvmarker.py + pyprof/parse/nvvp.py).
* :class:`StepWaterfall` — per-step wall attribution over the
  canonical components ``data_load`` / ``dispatch`` /
  ``device_compute`` (the async-dispatch ``block_until_ready``
  boundary: host time spent WAITING on the device, not device busy
  time) / ``telemetry_drain`` / ``ckpt_io`` plus the ``other``
  residual, emitted per step as one ``attr`` event with
  ``wall_ms = Σ parts`` and ``wall_device_ratio`` — ROADMAP item 2's
  exit criterion ("wall/device > 0.9") as a per-step number.
* :class:`DeviceMetricsBuffer` / :class:`DeferredTelemetry` —
  sync-free telemetry: per-step scalars (loss, grad-norm,
  overflow/skip state from :class:`~apex_tpu.amp.mixed_precision.
  StepInfo`) accumulate into a device-resident ring **inside the
  jitted step** and drain to the :class:`~apex_tpu.monitor.
  step_monitor.StepMonitor` every K steps through one explicit
  ``jax.device_get`` — zero per-step host transfers, provable under
  ``analysis.sanitize(transfer_guard="disallow",
  transfer_scope="device_to_host")``.  At K=1 the drained values are
  bitwise-identical to the synchronous per-step readbacks.
* :class:`CaptureTrigger` — on-demand profiling: a file-touch or
  SIGUSR1 trigger opens a :class:`apex_tpu.pyprof.ProfileWindow` for N
  steps mid-run (exactly one window per trigger), plus auto-capture
  when ``wall_device_ratio`` falls below the
  ``APEX_TPU_TRACE_RATIO_MIN`` registry flag — the waterfall's sibling
  of the Watchdog's stall-trace hook.

All clocks are injectable (fake-clock tests in
tests/test_monitor_tracing.py); every flag is registered in
:mod:`apex_tpu.analysis.flags`.  Full story with a worked waterfall
read: docs/api/observability.md.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

from ..analysis.flags import flag_float, flag_int, flag_str
from ..utils.log_util import get_logger
from .events import Event, Sink, terminal_reason

logger = get_logger(__name__)

__all__ = [
    "Span", "SpanTracer", "get_tracer", "set_tracer", "span",
    "recording", "watch_collector",
    "StepWaterfall", "WATERFALL_PARTS",
    "DeviceMetricsBuffer", "MetricsBufferState", "DeferredTelemetry",
    "CaptureTrigger", "TraceSession",
    "chrome_trace_from_events", "write_chrome_trace", "check_trace",
    "SERVE_PHASES", "serve_lane_events", "serve_lanes_from_events",
    "serve_chrome_trace", "check_serve_trace",
]


# ---------------------------------------------------------------------------
# Host span tracer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Span:
    """One completed host span.  ``t0`` is epoch seconds (wall-anchored
    monotonic time — see :class:`SpanTracer`), ``dur`` seconds."""

    name: str
    t0: float
    dur: float
    pid: int
    tid: int
    thread: str
    depth: int
    step: Optional[int] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_event(self) -> Event:
        attrs = {"t0": round(self.t0, 6), "tid": self.tid,
                 "thread": self.thread, "depth": self.depth}
        attrs.update(self.attrs)
        return Event(time=self.t0 + self.dur, step=self.step,
                     kind="span", name=self.name, value=self.dur,
                     attrs=attrs)

    def chrome_event(self) -> dict:
        ev = {"name": self.name, "ph": "X", "cat": "host",
              "ts": round(self.t0 * 1e6, 3),
              "dur": round(self.dur * 1e6, 3),
              "pid": self.pid, "tid": self.tid}
        args = dict(self.attrs)
        if self.step is not None:
            args["step"] = self.step
        if args:
            ev["args"] = args
        return ev


class _SpanHandle(contextlib.ContextDecorator):
    """Context-manager *and* decorator for one span occurrence —
    ``with tracer.span("x"):`` and ``@tracer.span("x")`` both work."""

    def __init__(self, tracer: "SpanTracer", name: str,
                 step: Optional[int], attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._step = step
        self._attrs = attrs
        self._t0 = None

    def __enter__(self):
        self._t0 = self._tracer._begin()
        return self

    def __exit__(self, *exc):
        self._tracer._end(self._name, self._t0, step=self._step,
                          attrs=self._attrs)
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span."""
        self._attrs.update(attrs)


class SpanTracer:
    """Near-zero-overhead host span recorder.

    Each completed span costs two monotonic clock reads and one
    list-append on a per-thread buffer (no lock on the hot path; the
    lock is only taken when a *new* thread first spans and at drain).
    Timing is ``time.perf_counter`` anchored once against the wall
    clock at construction, so exported spans carry epoch timestamps
    without paying a wall-clock syscall per span — the property that
    lets Perfetto line host spans up against a ``jax.profiler`` device
    trace captured in the same process.

    Nesting is tracked per thread (``depth``); the tracer is safe to
    use concurrently from any number of threads.
    """

    def __init__(self, *, clock: Callable[[], float] = time.perf_counter,
                 wall_clock: Callable[[], float] = time.time,
                 max_spans: int = 1_000_000):
        self._clock = clock
        # one wall anchor: epoch = anchor + (perf_counter - perf0)
        self._perf0 = clock()
        self._wall0 = wall_clock()
        self._pid = os.getpid()
        self._local = threading.local()
        self._lock = threading.Lock()
        self._buffers: List[List[Span]] = []
        self._max_spans = int(max_spans)
        self._dropped = 0

    # -- hot path ------------------------------------------------------------

    def _thread_buf(self) -> Tuple[List[Span], List[int]]:
        buf = getattr(self._local, "buf", None)
        if buf is None:
            buf = self._local.buf = []
            self._local.depth = [0]
            with self._lock:
                self._buffers.append(buf)
        return buf, self._local.depth

    def _begin(self) -> float:
        _, depth = self._thread_buf()
        depth[0] += 1
        return self._clock()

    def _end(self, name: str, t0: float, *, step=None, attrs=None) -> None:
        t1 = self._clock()
        buf, depth = self._thread_buf()
        depth[0] -= 1
        if len(buf) >= self._max_spans:
            # cold path (the buffer is already full): the shared drop
            # counter takes the lock — += from concurrent threads
            # loses counts (APX801)
            with self._lock:
                self._dropped += 1
            return
        th = threading.current_thread()
        buf.append(Span(
            name=name, t0=self._wall0 + (t0 - self._perf0),
            dur=t1 - t0, pid=self._pid, tid=th.ident or 0,
            thread=th.name, depth=depth[0], step=step,
            attrs=attrs or {}))

    def span(self, name: str, *, step: Optional[int] = None,
             **attrs) -> _SpanHandle:
        """``with tracer.span("data_load"): ...`` — also usable as a
        decorator (``@tracer.span("load_batch")``)."""
        return _SpanHandle(self, name, step, attrs)

    def add_complete(self, name: str, t0: float, dur: float, *,
                     tid: Optional[int] = None, thread: str = "",
                     step: Optional[int] = None, **attrs) -> None:
        """Record an externally-timed complete span (``t0`` epoch
        seconds) — how :meth:`apex_tpu.transformer.pipeline_parallel.
        utils.Timers.chrome_events` and the waterfall feed accumulated
        times into the same Chrome writer."""
        buf, _ = self._thread_buf()
        if len(buf) >= self._max_spans:
            with self._lock:
                self._dropped += 1
            return
        th = threading.current_thread()
        buf.append(Span(name=name, t0=float(t0), dur=float(dur),
                        pid=self._pid,
                        tid=th.ident if tid is None else int(tid),
                        thread=thread or th.name, depth=0, step=step,
                        attrs=attrs))

    def now(self) -> float:
        """Current time on the tracer's epoch-anchored timeline."""
        return self._wall0 + (self._clock() - self._perf0)

    # -- drain / export ------------------------------------------------------

    def drain(self) -> List[Span]:
        """Remove and return every recorded span (all threads),
        t0-ordered.  Only the snapshotted prefix of each per-thread
        buffer is deleted — an append racing in from the owning thread
        (the hot path is deliberately lock-free) lands at the tail and
        survives for the next drain instead of being silently lost."""
        out: List[Span] = []
        with self._lock:
            for buf in self._buffers:
                got = buf[:]
                out.extend(got)
                del buf[:len(got)]
        out.sort(key=lambda s: s.t0)
        return out

    def events(self, sink, step: Optional[int] = None) -> int:
        """Drain into a sink (anything with ``emit(Event)``) as
        ``span`` events; returns the number emitted.  Spans recorded
        without a step inherit ``step``."""
        spans = self.drain()
        for s in spans:
            if s.step is None and step is not None:
                s = dataclasses.replace(s, step=step)
            sink.emit(s.to_event())
        return len(spans)

    def chrome_trace(self, spans: Optional[List[Span]] = None) -> dict:
        """Chrome trace-event JSON object (load in Perfetto /
        chrome://tracing next to a ``jax.profiler`` dump).  Without
        ``spans``, drains the tracer."""
        if spans is None:
            spans = self.drain()
        with self._lock:
            dropped = self._dropped
        return _chrome_json([s.chrome_event() for s in spans],
                            pid=self._pid, dropped=dropped)

    def write_chrome_trace(self, path: str,
                           spans: Optional[List[Span]] = None) -> str:
        """Write :meth:`chrome_trace` atomically (scratch + rename)
        and return ``path``."""
        return write_chrome_trace(path, self.chrome_trace(spans))


_GLOBAL_TRACER: Optional[SpanTracer] = None


def get_tracer() -> Optional[SpanTracer]:
    """The process-wide tracer, or None when tracing is off."""
    return _GLOBAL_TRACER


def set_tracer(tracer: Optional[SpanTracer]) -> None:
    """Publish (or clear, with None) the process-wide tracer.  It also
    takes the collector's hook out (:func:`watch_collector`): a new
    recorder gets ``apex.host.gc`` spans once a serving step re-arms it,
    and none from a hook that an earlier profiler session left."""
    global _GLOBAL_TRACER
    _GLOBAL_TRACER = tracer
    watch_collector(False)


class _ProfilerSpan(TraceAnnotation):
    """What :func:`span` returns with no tracer installed: the bare
    profiler annotation (two calls into jaxlib, no Python frame)."""

    __slots__ = ()
    set = TraceAnnotation.set_metadata


class _TracedSpan(contextlib.ContextDecorator):
    """What :func:`span` returns with a tracer installed: the profiler
    annotation and the tracer's record of the same occurrence (a new
    pair on each entry, so the decorator form works)."""

    def __init__(self, tracer: SpanTracer, name: str,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        self._annotation = TraceAnnotation(self._name, **self._attrs)
        self._record = self._tracer.span(self._name, **self._attrs)
        self._annotation.__enter__()
        self._record.__enter__()
        return self

    def __exit__(self, *exc):
        self._record.__exit__(*exc)
        self._annotation.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        self._annotation.set_metadata(**attrs)
        self._record.set(**attrs)


def span(name: str, **attrs):
    """``with span("apex.<layer>.<phase>") as s:`` — the program's one
    host-span primitive, so library code instruments unconditionally.

    It always opens a ``jax.profiler.TraceAnnotation(name, **attrs)``:
    inert unless a profiler session is on, and then an event on the
    device trace's own clock, with ``attrs`` as its stats.  When a
    :class:`SpanTracer` is installed the same occurrence is also
    recorded there (JSONL ``span`` events, Chrome export).
    ``s.set(**attrs)`` adds attributes known only inside the span, to
    both; gather them under :func:`recording` so that a run with
    tracing off builds nothing.  Each call allocates one small object
    (an annotation enter/exit is about half a microsecond with the
    profiler off)."""
    t = _GLOBAL_TRACER
    if t is None:
        return _ProfilerSpan(name, **attrs)
    return _TracedSpan(t, name, attrs)


def recording() -> bool:
    """Whether a :func:`span` opened now lands anywhere: a profiler
    session is on, or a tracer is installed."""
    return _GLOBAL_TRACER is not None or TraceAnnotation.is_enabled()


_OPEN_COLLECTION: List[Any] = []     # the span of the collection running


def _collection_span(phase: str, info: Dict[str, Any]) -> None:
    """The ``gc.callbacks`` hook: ``apex.host.gc`` (stat ``generation``)
    from a collection's start to its stop.  CPython runs one collection
    at a time and calls the hook on the thread that collects, so the
    span lands on that thread's line."""
    if phase == "start":
        s = span("apex.host.gc", generation=info["generation"])
        s.__enter__()
        _OPEN_COLLECTION.append(s)
    elif _OPEN_COLLECTION:
        _OPEN_COLLECTION.pop().__exit__(None, None, None)


def watch_collector(on: bool) -> None:
    """Put the collector's hook into ``gc.callbacks`` (``on``) or take
    it out; idempotent and process-wide.  ``ServingEngine.step()`` calls
    it with :func:`recording` at its start, so the hook is there while a
    profiler or a tracer records, and with tracing off ``gc.callbacks``
    holds nothing of ours (jax keeps a callback of its own there)."""
    hooked = _collection_span in gc.callbacks
    if on and not hooked:
        gc.callbacks.append(_collection_span)
    elif hooked and not on:
        gc.callbacks.remove(_collection_span)


def _chrome_json(events: List[dict], *, pid: int,
                 dropped: int = 0) -> dict:
    meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": "apex_tpu host"}}]
    out = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
    if dropped:
        out["otherData"] = {"dropped_spans": dropped}
    return out


def write_chrome_trace(path: str, trace: dict) -> str:
    """Atomic Chrome-trace write: scratch file then ``os.replace`` so a
    kill mid-write never leaves a truncated artifact."""
    scratch = path + ".partial"
    with open(scratch, "w") as f:
        json.dump(trace, f, separators=(",", ":"))
    os.replace(scratch, path)
    return path


def chrome_trace_from_events(events) -> dict:
    """Rebuild a Chrome trace from a monitor event log: ``span`` events
    become host ``X`` (complete) events; ``timer`` events (phase times
    exported by ``Timers.events`` — value in seconds, stamped at stop)
    become complete events ending at their emission time on a synthetic
    ``timers`` track; serving ``request_done`` lifecycle events become
    one per-request lane each with queued/prefill/decode phases
    (:func:`serve_lanes_from_events`).  The read-side join: any
    committed run JSONL can be turned back into a Perfetto-loadable
    timeline (``tools/monitor_summary.py --chrome OUT.json``)."""
    pid = os.getpid()
    out: List[dict] = []
    timer_tid = 1
    for e in events:
        if e.kind == "span" and isinstance(e.value, (int, float)):
            t0 = e.attrs.get("t0", e.time - float(e.value))
            ev = {"name": e.name, "ph": "X", "cat": "host",
                  "ts": round(float(t0) * 1e6, 3),
                  "dur": round(float(e.value) * 1e6, 3),
                  "pid": pid, "tid": e.attrs.get("tid", 0)}
            args = {k: v for k, v in e.attrs.items()
                    if k not in ("t0", "tid")}
            if e.step is not None:
                args["step"] = e.step
            if args:
                ev["args"] = args
            out.append(ev)
        elif e.kind == "timer" and isinstance(e.value, (int, float)):
            dur = float(e.value)
            ev = {"name": e.name, "ph": "X", "cat": "timer",
                  "ts": round((e.time - dur) * 1e6, 3),
                  "dur": round(dur * 1e6, 3),
                  "pid": pid, "tid": timer_tid}
            if e.step is not None:
                ev["args"] = {"step": e.step}
            out.append(ev)
    out.extend(serve_lanes_from_events(events, pid=pid))
    return _chrome_json(out, pid=pid)


# ---------------------------------------------------------------------------
# Serving request lanes (apex_tpu.serving.metrics is the write side)
# ---------------------------------------------------------------------------

#: Per-request lane phases, in lifecycle order.  ``queued`` is
#: submit → admission start, ``prefill`` admission → first token,
#: ``decode`` first token → terminal — contiguous sub-intervals of the
#: request wall, so the lane IS the request's waterfall.
SERVE_PHASES = ("queued", "prefill", "decode")

#: tid offset for request lanes so they sort below the host-span and
#: timer tracks in Perfetto
_SERVE_LANE_TID0 = 1000


def serve_lane_events(rows: List[dict], *,
                      pid: Optional[int] = None) -> List[dict]:
    """Chrome trace events (one lane per request) from lane rows —
    ``{rid, end (epoch s), queue_wait_ms, prefill_ms, decode_ms,
    new_tokens, preempted, tick}`` as produced by
    :meth:`apex_tpu.serving.metrics.RequestTrace.lane_row` (exact
    timestamps) or reconstructed from terminal events
    (:func:`serve_lanes_from_events`).  ``prefill_ms``/``decode_ms``
    are None for a request preempted before admission (its lane is
    queue wait only)."""
    pid = os.getpid() if pid is None else pid
    out: List[dict] = []
    for i, r in enumerate(rows):
        if r.get("end") is None:
            continue
        tid = _SERVE_LANE_TID0 + i
        out.append({"name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": f"req {r['rid']}"}})
        parts = [(p, r.get(_ATTR_FOR_PHASE[p]))
                 for p in SERVE_PHASES]
        total_ms = sum(v for _, v in parts
                       if isinstance(v, (int, float)))
        t = r["end"] * 1e6 - total_ms * 1e3   # lane start, us
        args = {"rid": r["rid"]}
        for k in ("new_tokens", "preempted", "tick"):
            if r.get(k) is not None:
                args[k] = r[k]
        for phase, ms in parts:
            if not isinstance(ms, (int, float)):
                continue
            out.append({"name": phase, "ph": "X", "cat": "serve",
                        "ts": round(t, 3),
                        "dur": round(ms * 1e3, 3),
                        "pid": pid, "tid": tid, "args": args})
            t += ms * 1e3
    return out


_ATTR_FOR_PHASE = {"queued": "queue_wait_ms", "prefill": "prefill_ms",
                   "decode": "decode_ms"}


def serve_lanes_from_events(events, *,
                            pid: Optional[int] = None) -> List[dict]:
    """Rebuild per-request Chrome lanes from a run JSONL's serving
    lifecycle events: each terminal ``request_done`` carries the whole
    queued/prefill/decode breakdown, anchored backwards from its own
    emission time.  (The write-side export —
    ``ServeMetrics.chrome_trace`` — uses the exact engine-clock
    timestamps instead; the two agree to within the emit latency.)"""
    rows = []
    for e in events:
        if e.kind != "serving" or e.name != "request_done":
            continue
        a = e.attrs
        rows.append({
            "rid": a.get("rid"),
            "end": e.time,
            "queue_wait_ms": a.get("queue_wait_ms"),
            "prefill_ms": (a.get("prefill_ms")
                           if "ttft_ms" in a else None),
            "decode_ms": (a.get("decode_ms")
                          if "ttft_ms" in a else None),
            "new_tokens": a.get("new_tokens"),
            "preempted": a.get("preempted"),
            "tick": e.step,
        })
    return serve_lane_events(rows, pid=pid)


def serve_chrome_trace(rows: List[dict]) -> dict:
    """Chrome trace-event JSON object holding only request lanes (the
    ``--serve --trace`` artifact; write with
    :func:`write_chrome_trace`)."""
    pid = os.getpid()
    return _chrome_json(serve_lane_events(rows, pid=pid), pid=pid)


def check_serve_trace(jsonl_path,
                      chrome_path: Optional[str] = None, *,
                      tolerance: float = 0.02) -> List[str]:
    """Validate a serve run's telemetry (``tools/trace_check.py
    --serve``, ci.sh step 10).  ``jsonl_path`` may be ONE path or a
    sequence of per-replica paths (``trace_check --serve
    serve-r0.jsonl serve-r1.jsonl ...`` — the ISSUE-14 fleet form):
    events merge before checking, so *N submitted ⇒ N terminal* holds
    across the whole fleet — a request routed to replica A and
    journal-replayed there still closes exactly once fleet-wide, and
    a rid appearing on two replicas' logs (a double submit the router
    must never produce) fails.  Returns failure strings (empty =
    pass):

    * lifecycle completeness — every submitted rid ends in exactly one
      terminal ``request_done`` (N submitted ⇒ N terminal events), no
      terminal without a submit.  This holds on EVERY terminal path:
      finished, drain-preempted, ``deadline``/``deadline_exceeded``
      expiry, ``shed``, and across a supervised crash-replay (a
      journal-replayed rid re-enters WITHOUT a second submit event,
      so the chain still closes exactly once);
    * TTFT present for every rid that *finished* (``request_first_
      token`` event + ``ttft_ms`` on the terminal); preempted / shed /
      deadline-expired requests may legitimately end before their
      first token;
    * per-request attribution — ``queue_wait + prefill + decode`` sums
      to the rid's ``wall_ms`` within ``tolerance``;
    * engine gauges — a run that decoded must carry ``serve_tick``
      events;
    * the live metrics plane (ISSUE-17) — every ``slo_burn`` alarm
      traces back to an ``slo_objectives`` definition event,
      ``fleet_tick`` steps are monotone non-decreasing per log, and
      ``metrics_server_started`` / ``metrics_server_stopped`` pair
      up (every started server was torn down, and vice versa);
    * the distributed control plane (ISSUE-18) — when supervisor
      ``replica_spawned`` events are present, every spawned
      ``(replica, incarnation)`` pairs with exactly one
      ``replica_reaped`` and vice versa (a kill-9'd incarnation is
      reaped before its replay incarnation spawns; a drained
      scale-down victim is reaped too — nothing leaks), and every
      ``autoscale`` event carries a valid ``action`` with its
      subject replica's lifecycle events in the log;
    * the Chrome artifact (when given) parses and carries one lane per
      terminal rid with the canonical queued/prefill/decode phases.
    """
    from .summary import load_events

    failures: List[str] = []
    paths = ([jsonl_path] if isinstance(jsonl_path, (str, os.PathLike))
             else list(jsonl_path))
    events = []
    for p in paths:
        evs, malformed = load_events(p)
        if malformed:
            failures.append(f"{malformed} malformed line(s) in {p}")
        # fleet aggregation rounds must advance in emission order
        # WITHIN each log (merged logs interleave legitimately)
        last_ft = None
        for e in evs:
            if e.kind == "fleet_tick":
                if last_ft is not None and e.step is not None \
                        and e.step < last_ft:
                    failures.append(
                        f"{p}: fleet_tick step went backwards "
                        f"({last_ft} -> {e.step})")
                if e.step is not None:
                    last_ft = e.step
        events.extend(evs)
    srv = [e for e in events if e.kind == "serving"]
    # ISSUE-17: every slo_burn alarm must trace back to an objective
    # definition event, and the exporter lifecycle must pair up
    burns = [e for e in events
             if e.kind == "alarm" and e.name == "slo_burn"]
    slo_defs = [e for e in events
                if e.kind == "slo" and e.name == "slo_objectives"]
    if burns and not slo_defs:
        failures.append(
            f"{len(burns)} slo_burn alarm(s) with no slo_objectives "
            f"definition event — burns must be attributable to a "
            f"declared objective")
    started = sum(1 for e in events if e.kind == "metrics"
                  and e.name == "metrics_server_started")
    stopped = sum(1 for e in events if e.kind == "metrics"
                  and e.name == "metrics_server_stopped")
    if started != stopped:
        failures.append(
            f"metrics_server_started ({started}) != "
            f"metrics_server_stopped ({stopped}) — every metrics "
            f"server must be torn down")
    # ISSUE-18: process-isolated fleet lifecycle — checks arm only
    # when a supervisor log is in the merge (single-process serve
    # runs have no spawn events and skip this block entirely)
    fleet = [e for e in events if e.kind == "fleet"]
    spawned_pairs: Dict[tuple, int] = {}
    reaped_pairs: Dict[tuple, int] = {}
    for e in fleet:
        key = (str(e.attrs.get("replica")),
               int(e.attrs.get("incarnation") or 0))
        if e.name == "replica_spawned":
            spawned_pairs[key] = spawned_pairs.get(key, 0) + 1
        elif e.name == "replica_reaped":
            reaped_pairs[key] = reaped_pairs.get(key, 0) + 1
    if spawned_pairs:
        for key, n in sorted(spawned_pairs.items()):
            if n != 1:
                failures.append(
                    f"replica {key[0]} incarnation {key[1]}: "
                    f"{n} replica_spawned events, want exactly 1")
            if reaped_pairs.get(key, 0) != 1:
                failures.append(
                    f"replica {key[0]} incarnation {key[1]}: "
                    f"spawned but {reaped_pairs.get(key, 0)} "
                    f"replica_reaped event(s) — every incarnation "
                    f"must be reaped exactly once")
        for key in sorted(set(reaped_pairs) - set(spawned_pairs)):
            failures.append(
                f"replica {key[0]} incarnation {key[1]}: "
                f"replica_reaped without a replica_spawned")
        known = {k[0] for k in spawned_pairs}
        for e in fleet:
            if e.name != "autoscale":
                continue
            action = e.attrs.get("action")
            if action not in ("up", "down"):
                failures.append(
                    f"autoscale event with invalid action "
                    f"{action!r} (want 'up' or 'down')")
            if str(e.attrs.get("replica")) not in known:
                failures.append(
                    f"autoscale {action} names replica "
                    f"{e.attrs.get('replica')!r} with no lifecycle "
                    f"events in the log")
    # fleet-mode sanity: one rid must live on exactly one replica —
    # its submit and terminal must carry the same replica stamp
    if len(paths) > 1:
        homes: Dict[str, set] = {}
        for e in srv:
            if e.name in ("request_submitted", "request_done") \
                    and e.attrs.get("replica") is not None:
                homes.setdefault(str(e.attrs.get("rid")),
                                 set()).add(str(e.attrs["replica"]))
        for rid, reps in sorted(homes.items()):
            if len(reps) > 1:
                failures.append(
                    f"rid {rid}: lifecycle events on "
                    f"{len(reps)} replicas ({sorted(reps)}) — a "
                    f"request must live on exactly one")
    submitted = [str(e.attrs.get("rid")) for e in srv
                 if e.name == "request_submitted"]
    terminal: Dict[str, int] = {}
    done_events = {}
    for e in srv:
        if e.name == "request_done":
            rid = str(e.attrs.get("rid"))
            terminal[rid] = terminal.get(rid, 0) + 1
            done_events[rid] = e
    first_token = {str(e.attrs.get("rid")) for e in srv
                   if e.name == "request_first_token"}
    if not submitted:
        failures.append("no request_submitted events — not a serve "
                        "run log?")
    for rid in submitted:
        n = terminal.get(rid, 0)
        if n != 1:
            failures.append(f"rid {rid}: {n} terminal request_done "
                            f"event(s), want exactly 1")
    for rid in terminal:
        if rid not in submitted:
            failures.append(f"rid {rid}: terminal event without a "
                            f"request_submitted")
    for rid, e in sorted(done_events.items()):
        a = e.attrs
        term = terminal_reason(a)
        if term == "finished":
            if "ttft_ms" not in a:
                failures.append(f"rid {rid}: finished without a "
                                f"ttft_ms — TTFT must exist for "
                                f"every finished request")
            if rid not in first_token:
                failures.append(f"rid {rid}: no request_first_token "
                                f"event in the chain")
        wall = a.get("wall_ms")
        if isinstance(wall, (int, float)) and wall > 0:
            parts = sum(float(a.get(k) or 0.0)
                        for k in ("queue_wait_ms", "prefill_ms",
                                  "decode_ms"))
            if abs(parts - wall) > tolerance * wall + 1e-3:
                failures.append(
                    f"rid {rid}: queued+prefill+decode "
                    f"{parts:.3f} ms != wall {wall:.3f} ms "
                    f"(> {tolerance:.0%})")
    decoded = any(e.name == "decode_step" for e in srv)
    gauges = [e for e in events if e.kind == "serve_tick"]
    if decoded and not gauges:
        failures.append("run decoded but emitted no serve_tick "
                        "engine gauges")
    if chrome_path is not None:
        try:
            with open(chrome_path) as f:
                trace = json.load(f)
            evs = trace.get("traceEvents", [])
            lanes: Dict[str, set] = {}
            for t in evs:
                if t.get("ph") == "X" and t.get("cat") == "serve":
                    rid = str((t.get("args") or {}).get("rid"))
                    lanes.setdefault(rid, set()).add(t.get("name"))
            for rid, e in sorted(done_events.items()):
                phases = lanes.get(rid)
                if phases is None:
                    failures.append(f"{chrome_path}: no lane for "
                                    f"rid {rid}")
                    continue
                want = {"queued"}
                if "ttft_ms" in e.attrs:
                    want = set(SERVE_PHASES)
                miss = sorted(want - phases)
                if miss:
                    failures.append(f"{chrome_path}: rid {rid} lane "
                                    f"missing phase(s) {miss}")
        except (OSError, json.JSONDecodeError) as e:
            failures.append(f"{chrome_path}: unreadable Chrome trace "
                            f"({e})")
    return failures


# ---------------------------------------------------------------------------
# Per-step wall-time waterfall
# ---------------------------------------------------------------------------

#: Canonical per-step components.  ``device_compute`` is measured from
#: the async-dispatch boundary: the time the host spends blocked in
#: ``block_until_ready`` on the step's outputs.  Everything not inside
#: a named part lands in the ``other`` residual, so the parts sum to
#: the step wall time *by construction*.
WATERFALL_PARTS = ("data_load", "dispatch", "device_compute",
                   "telemetry_drain", "ckpt_io")


class StepWaterfall:
    """Per-step wall-time attribution over :data:`WATERFALL_PARTS`.

    Usage (the shared smoke-loop shape)::

        wf.begin_step(i)
        with wf.part("dispatch"):
            out = step_fn(...)          # returns at enqueue (async)
        with wf.part("device_compute"):
            jax.block_until_ready(loss)  # the device boundary
        ...
        row = wf.end_step(sink, step=i)  # one 'attr' event

    ``end_step`` computes ``wall_ms``, per-part ms, the ``other``
    residual (``wall - Σ parts``, >= 0 by construction since parts are
    disjoint sub-intervals of the step window) and
    ``wall_device_ratio = device_compute / wall``.  With a
    :class:`SpanTracer` attached, each part is also recorded as a span
    so the waterfall appears in the Chrome trace.
    """

    def __init__(self, tracer: Optional[SpanTracer] = None, *,
                 clock: Callable[[], float] = time.perf_counter,
                 on_row: Optional[Callable[[dict], None]] = None):
        self._tracer = tracer
        self._clock = clock
        self._on_row = on_row
        self._t0: Optional[float] = None
        self._step: Optional[int] = None
        self._parts: Dict[str, float] = {}
        self.rows: List[dict] = []

    def begin_step(self, step: Optional[int] = None) -> None:
        self._t0 = self._clock()
        self._step = step
        self._parts = {}

    @contextlib.contextmanager
    def part(self, name: str):
        """Attribute the enclosed block to component ``name`` (repeat
        entries accumulate).  Unknown names are allowed — they appear
        as extra components in the row."""
        if self._t0 is None:
            # not inside a step: still time it, attributed on emit as
            # a standalone span only
            if self._tracer is not None:
                with self._tracer.span(name):
                    yield
            else:
                yield
            return
        span_ctx = (self._tracer.span(name, step=self._step)
                    if self._tracer is not None
                    else contextlib.nullcontext())
        t0 = self._clock()
        try:
            with span_ctx:
                yield
        finally:
            self._parts[name] = (self._parts.get(name, 0.0)
                                 + self._clock() - t0)

    def end_step(self, sink=None, step: Optional[int] = None,
                 **extra) -> dict:
        """Close the step: compute the attribution row, emit it as one
        ``attr`` event into ``sink`` (when given), invoke the ``on_row``
        hook (auto-capture wiring), and return it.  ``extra`` keyword
        values are merged into the row (and the event attrs) verbatim —
        how the scan driver stamps ``scan_k`` (steps per dispatch) on a
        window's row; names must not end in ``_ms`` (those are reserved
        for the parts-sum-to-wall invariant)."""
        if self._t0 is None:
            raise RuntimeError("end_step without begin_step")
        bad = [k for k in extra if k.endswith("_ms")]
        if bad:
            raise ValueError(f"extra row field(s) {bad} collide with "
                             "the *_ms attribution namespace")
        wall = self._clock() - self._t0
        if step is None:
            step = self._step
        parts = dict(self._parts)
        other = max(0.0, wall - sum(parts.values()))
        row: Dict[str, Any] = {"step": step,
                               "wall_ms": wall * 1e3}
        row.update(extra)
        for name in WATERFALL_PARTS:
            row[f"{name}_ms"] = parts.pop(name, 0.0) * 1e3
        for name, v in sorted(parts.items()):  # non-canonical extras
            row[f"{name}_ms"] = v * 1e3
        row["other_ms"] = other * 1e3
        row["wall_device_ratio"] = (
            row["device_compute_ms"] / row["wall_ms"]
            if wall > 0.0 else 0.0)
        self._t0 = None
        self.rows.append(row)
        if sink is not None:
            attrs = {k: round(v, 4) if isinstance(v, float) else v
                     for k, v in row.items()
                     if k not in ("step", "wall_ms")}
            sink.emit(Event(time=time.time(), step=step, kind="attr",
                            name="step_waterfall",
                            value=round(row["wall_ms"], 4),
                            attrs=attrs))
        if self._on_row is not None:
            try:
                self._on_row(row)
            except Exception as e:
                logger.warning("waterfall on_row hook failed: %s",
                               str(e)[:160])
        return row


# ---------------------------------------------------------------------------
# Sync-free deferred telemetry
# ---------------------------------------------------------------------------

class MetricsBufferState(NamedTuple):
    """Device-resident ring state — a pytree, so it threads through a
    jitted step (and donates) like any other carry."""

    values: Any   # f32 [capacity, n_metrics]
    count: Any    # i32 scalar: total appends since init


class DeviceMetricsBuffer:
    """Fixed-capacity device ring of per-step scalar metrics.

    ``append`` is pure jnp (trace-safe — call it *inside* the jitted
    step); ``drain`` performs the only host transfer, one **explicit**
    ``jax.device_get`` of the whole ring, which the transfer guard's
    ``disallow`` level (implicit transfers) permits — that asymmetry is
    the zero-per-step-transfer proof ``analysis.sanitizer`` enforces.

    Values are stored as float32; at drain they convert to Python
    floats exactly, so a K=1 drain is bitwise-identical to the
    synchronous ``float(loss)`` readback it replaces.
    """

    DEFAULT_METRICS = ("loss", "grad_norm", "loss_scale", "overflow",
                       "steps_skipped")

    def __init__(self, capacity: int,
                 metrics: Tuple[str, ...] = DEFAULT_METRICS):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.metrics = tuple(metrics)

    def init(self) -> MetricsBufferState:
        import jax.numpy as jnp

        return MetricsBufferState(
            values=jnp.zeros((self.capacity, len(self.metrics)),
                             jnp.float32),
            count=jnp.zeros((), jnp.int32))

    def append(self, state: MetricsBufferState,
               **metrics) -> MetricsBufferState:
        """Append one row (trace-safe).  Every registered metric must
        be supplied; extras are rejected so a typo cannot silently
        drop a series."""
        import jax
        import jax.numpy as jnp

        unknown = set(metrics) - set(self.metrics)
        if unknown:
            raise ValueError(f"unregistered metric(s) {sorted(unknown)}; "
                             f"buffer records {self.metrics}")
        row = jnp.stack([
            jnp.asarray(metrics[m]).astype(jnp.float32).reshape(())
            for m in self.metrics])
        idx = jnp.mod(state.count, self.capacity)
        values = jax.lax.dynamic_update_slice(
            state.values, row[None, :], (idx, jnp.int32(0)))
        return MetricsBufferState(values=values, count=state.count + 1)

    def drain(self, state: MetricsBufferState,
              drained: int) -> Tuple[int, List[Tuple[int, Dict[str, float]]]]:
        """One explicit device→host fetch of the ring.  ``drained`` is
        how many appends previous drains consumed; returns the new
        count and ``[(append_index, {metric: value}), ...]`` for every
        un-drained row still resident (overwritten rows — more than
        ``capacity`` appends since the last drain — are lost and
        logged, never silently renumbered)."""
        import jax

        host = jax.device_get(state)
        count = int(host.count)
        start = max(int(drained), count - self.capacity)
        if start > drained:
            logger.warning(
                "DeviceMetricsBuffer overran: %d row(s) overwritten "
                "before drain (capacity %d)", start - drained,
                self.capacity)
        rows = []
        for j in range(start, count):
            vals = host.values[j % self.capacity]
            rows.append((j, {m: float(v)
                             for m, v in zip(self.metrics, vals)}))
        return count, rows


class DeferredTelemetry:
    """Loop-side manager for a :class:`DeviceMetricsBuffer`: threads
    the ring state through a deferred step function, drains every
    ``every`` appends, and emits the drained rows as the same
    ``metric`` / ``scale`` events the synchronous path produces (same
    names, same values — the step numbers are reconstructed from append
    order, so a deferred log summarizes identically).
    """

    def __init__(self, every: int, *,
                 buffer: Optional[DeviceMetricsBuffer] = None):
        self.every = max(1, int(every))
        self.buffer = buffer or DeviceMetricsBuffer(
            capacity=self.every)
        self.state = self.buffer.init()
        self._drained = 0
        self._drain_count = 0
        self._steps: List[int] = []   # step number per pending append
        self.last_metrics: Optional[Dict[str, float]] = None

    def step(self, step_fn, params, amp_state, *, step: int):
        """Run one deferred step: ``step_fn(params, amp_state, tstate)
        -> (params, amp_state, tstate, loss, gnorm, info)`` (the shape
        ``build_train_step(..., telemetry=buf)`` produces).  Keeps the
        returned ring state; no host transfer."""
        params, amp_state, self.state, loss, gnorm, info = step_fn(
            params, amp_state, self.state)
        self._steps.append(step)
        return params, amp_state, loss, gnorm, info

    def scan_window(self, step_fn, params, amp_state, *, start: int,
                    k: int):
        """Run one K-step scan window: ``step_fn(params, amp_state,
        tstate) -> (params, amp_state, tstate, loss, gnorm, info)``
        where the jitted body appended ``k`` rows to the ring (the
        shape ``build_train_step_scan(setup, k, telemetry=buf)``
        produces).  Records the window's step numbers
        ``[start, start+k)`` for drain-time renumbering; no host
        transfer.  The ring must hold a full window
        (``buffer.capacity >= k``) or rows would be overwritten before
        the drain."""
        if k > self.buffer.capacity:
            raise ValueError(
                f"scan window of {k} steps exceeds the telemetry ring "
                f"capacity {self.buffer.capacity}")
        params, amp_state, self.state, loss, gnorm, info = step_fn(
            params, amp_state, self.state)
        self._steps.extend(range(start, start + k))
        return params, amp_state, loss, gnorm, info

    @property
    def pending(self) -> int:
        return len(self._steps)

    @property
    def drains(self) -> int:
        """Completed drains so far (the ceil(N/K) proof counter)."""
        return self._drain_count

    def maybe_drain(self, monitor, force: bool = False) -> int:
        """Drain if ``every`` appends accumulated (or ``force``).
        Returns the number of rows emitted.  Each actual drain also
        emits one ``telemetry``/``telemetry_drain`` event (rows +
        drain ordinal) so a log proves the drain cadence — the
        ceil(N/K) count the scan-driver CI smoke asserts."""
        if not self._steps or (not force
                               and len(self._steps) < self.every):
            return 0
        count, rows = self.buffer.drain(self.state, self._drained)
        base = self._drained
        emitted = 0
        for j, metrics in rows:
            step = self._steps[j - base]
            self._emit_row(monitor, step, metrics)
            emitted += 1
        self._steps = self._steps[count - base:]
        self._drained = count
        self._drain_count += 1
        ev = getattr(monitor, "event", None)
        if ev is not None:
            ev("telemetry", "telemetry_drain", value=float(emitted),
               step=None, drain=self._drain_count, forced=bool(force))
        return emitted

    def _emit_row(self, monitor, step: int,
                  metrics: Dict[str, float]) -> None:
        self.last_metrics = dict(metrics, step=step)
        for name in ("loss", "grad_norm"):
            if name in metrics:
                monitor.event("metric", name, value=metrics[name],
                              step=step)
        if "loss_scale" in metrics:
            monitor.event("scale", "loss_scale",
                          value=metrics["loss_scale"], step=step,
                          steps_skipped=int(metrics.get(
                              "steps_skipped", 0)),
                          deferred=True)
        overflow = metrics.get("overflow")
        if overflow is not None and overflow > 0.5:
            monitor.event("scale", "overflow", value=1.0, step=step)
        wd = getattr(monitor, "watchdog", None)
        if wd is not None:
            wd.observe_step(step, loss=metrics.get("loss"),
                            overflow=None if overflow is None
                            else overflow > 0.5)


# ---------------------------------------------------------------------------
# On-demand capture
# ---------------------------------------------------------------------------

class CaptureTrigger:
    """Open a profiling window mid-run, on demand.

    Three trigger sources, each opening **exactly one** window per
    firing (re-triggers while a window is open are ignored):

    * file touch — ``trigger_file`` exists at a step boundary (the
      file is consumed);
    * SIGUSR1 (or any ``signum``) — the handler only sets a flag; the
      window opens at the next step boundary (same discipline as
      :class:`apex_tpu.resilience.AutoResume`);
    * auto-capture — :meth:`observe_ratio` requests a window when the
      waterfall's ``wall_device_ratio`` drops below ``ratio_min``
      (once per run by default: the first bad step is the evidence;
      continuous re-capture would *be* host overhead).

    The window is a :class:`apex_tpu.pyprof.ProfileWindow` over
    ``steps`` iterations (injectable ``window_factory`` for tests);
    lifecycle is recorded as ``trace`` events
    (``capture_requested`` / ``capture_started`` / ``capture_stopped``)
    so ``tools/monitor_summary.py`` can index captured traces.
    """

    def __init__(self, logdir: str, *, steps: int = 4,
                 trigger_file: Optional[str] = None,
                 signum: Optional[int] = None,
                 ratio_min: float = 0.0,
                 max_auto_captures: int = 1,
                 window_factory=None, sink: Optional[Sink] = None,
                 timers=None):
        self.logdir = logdir
        self.steps = max(1, int(steps))
        self.trigger_file = trigger_file
        self.ratio_min = float(ratio_min)
        self._max_auto = int(max_auto_captures)
        self._auto_done = 0
        self._sink = sink
        self._timers = timers
        if window_factory is None:
            from ..pyprof.profile import ProfileWindow

            window_factory = ProfileWindow
        self._factory = window_factory
        self._pending: Optional[str] = None  # trigger reason
        self._window = None
        self._window_stop = 0
        self._window_dir: Optional[str] = None
        self.captures = 0
        self._signum = signum
        self._prev_handler = None
        if signum is not None:
            import signal as _signal

            try:
                self._prev_handler = _signal.signal(
                    signum, lambda *_: self.request("signal"))
            except ValueError as e:
                # signal.signal only works on the main thread — a
                # trigger built elsewhere keeps its file/ratio sources
                logger.warning("signal trigger unavailable: %s",
                               str(e)[:120])
                self._signum = None

    def _event(self, name: str, step=None, **attrs) -> None:
        if self._sink is None:
            return
        self._sink.emit(Event(time=time.time(), step=step,
                              kind="trace", name=name, attrs=attrs))

    def request(self, reason: str) -> None:
        """Arm a capture; the window opens at the next ``poll``."""
        if self._pending is None and self._window is None:
            self._pending = reason

    def observe_ratio(self, ratio: Optional[float],
                      step: Optional[int] = None) -> None:
        """Auto-capture hook — wire as the waterfall's ``on_row`` via
        ``lambda row: trigger.observe_ratio(row["wall_device_ratio"],
        row["step"])``."""
        if (self.ratio_min <= 0.0 or ratio is None
                or ratio >= self.ratio_min
                or self._auto_done >= self._max_auto):
            return
        if self._pending is not None or self._window is not None:
            # a capture is already armed/open: the request would be
            # dropped, so the once-per-run budget must not be spent —
            # a later genuine degradation still gets its window
            return
        self._auto_done += 1
        self._event("capture_requested", step=step,
                    reason="wall_device_ratio", ratio=round(ratio, 4),
                    threshold=self.ratio_min)
        self.request("wall_device_ratio")

    def poll(self, iteration: int) -> None:
        """Call once per step boundary: consume triggers, open/step/
        close the window."""
        if (self.trigger_file is not None and self._pending is None
                and self._window is None
                and os.path.exists(self.trigger_file)):
            try:
                os.unlink(self.trigger_file)
            except OSError as e:
                logger.warning("capture trigger file unlink failed: %s",
                               str(e)[:120])
            self._event("capture_requested", step=iteration,
                        reason="file", path=self.trigger_file)
            self.request("file")
        if self._pending is not None and self._window is None:
            reason, self._pending = self._pending, None
            if reason == "signal":
                # the handler only sets the flag (telemetry from a
                # signal context is unsafe); the request event is
                # emitted here, at the step boundary that consumes it,
                # so the requested/opened accounting covers all three
                # trigger sources
                self._event("capture_requested", step=iteration,
                            reason="signal")
            start, stop = iteration, iteration + self.steps
            self._window_dir = os.path.join(
                self.logdir, f"capture_step{start}")
            try:
                self._window = self._factory(
                    self._window_dir, start, stop, timers=self._timers)
                self._window_stop = stop
                self.captures += 1
                self._event("capture_started", step=iteration,
                            reason=reason, trace_dir=self._window_dir,
                            start=start, stop=stop)
            except Exception as e:  # capture must never kill the run
                logger.warning("capture window failed to open: %s",
                               str(e)[:160])
                self._window = None
        if self._window is not None:
            try:
                self._window.step(iteration)
            except Exception as e:
                logger.warning("capture window step failed: %s",
                               str(e)[:160])
                # close the wreck: an abandoned window would leave the
                # global jax.profiler session open, breaking every
                # later capture and charging profiling overhead to the
                # rest of the run
                try:
                    self._window.close()
                except Exception as e2:
                    logger.warning("capture window close after step "
                                   "failure also failed: %s",
                                   str(e2)[:160])
                self._window = None
                self._event("capture_stopped", step=iteration,
                            trace_dir=self._window_dir,
                            error=str(e)[:160])
                return
            if iteration >= self._window_stop:
                self._window = None
                self._event("capture_stopped", step=iteration,
                            trace_dir=self._window_dir)

    def close(self) -> None:
        """Tear down: close an open window, restore the signal
        handler."""
        if self._window is not None:
            try:
                self._window.close()
            except Exception as e:
                logger.warning("capture window close failed: %s",
                               str(e)[:160])
            self._event("capture_stopped", trace_dir=self._window_dir,
                        at_close=True)
            self._window = None
        if self._signum is not None and self._prev_handler is not None:
            import signal as _signal

            _signal.signal(self._signum, self._prev_handler)
            self._prev_handler = None


# ---------------------------------------------------------------------------
# Session bundle — what the drivers wire
# ---------------------------------------------------------------------------

class TraceSession:
    """Tracer + waterfall + optional capture trigger, built together
    so a driver enables the whole attribution story with one object
    (``--trace DIR`` in the smoke drivers).  ``close`` flushes the
    remaining spans into the sink and writes the Chrome artifact
    (``<dir>/trace.chrome.json``, atomic)."""

    def __init__(self, directory: Optional[str] = None, *,
                 tracer: Optional[SpanTracer] = None,
                 capture: Optional[CaptureTrigger] = None,
                 on_row=None, max_spans: int = 250_000):
        self.directory = directory
        self.tracer = tracer or SpanTracer()
        self.capture = capture
        # bound on the session-lifetime span list backing the Chrome
        # artifact — an always-on ambient trace over a long run must
        # not grow host memory without limit (the JSONL events are the
        # complete record; the Chrome file keeps the first max_spans)
        self._max_spans = int(max_spans)
        self._session_dropped = 0

        def _row(row):
            if self.capture is not None:
                self.capture.observe_ratio(row.get("wall_device_ratio"),
                                           row.get("step"))
            if on_row is not None:
                on_row(row)

        self.waterfall = StepWaterfall(self.tracer, on_row=_row)
        self._all_spans: List[Span] = []

    @classmethod
    def from_flags(cls, directory: str, *, sink=None,
                   timers=None) -> "TraceSession":
        """Build from the ``APEX_TPU_TRACE_*`` registry flags.  The
        capture trigger is always armed on a traced run — SIGUSR1
        must open a window (not kill the process via the default
        disposition) whenever tracing is on, as the docs promise; the
        file trigger and the ratio auto-capture additionally engage
        when their flags are set."""
        import signal as _signal

        capture = CaptureTrigger(
            os.path.join(directory, "captures"),
            steps=flag_int("APEX_TPU_TRACE_CAPTURE_STEPS"),
            trigger_file=flag_str("APEX_TPU_TRACE_CAPTURE_FILE"),
            signum=getattr(_signal, "SIGUSR1", None),
            ratio_min=flag_float("APEX_TPU_TRACE_RATIO_MIN"),
            sink=sink, timers=timers)
        return cls(directory, capture=capture)

    def _keep(self, spans: List[Span]) -> None:
        room = self._max_spans - len(self._all_spans)
        if room >= len(spans):
            self._all_spans.extend(spans)
        else:
            if room > 0:
                self._all_spans.extend(spans[:room])
            self._session_dropped += len(spans) - max(room, 0)

    def flush(self, sink, step: Optional[int] = None) -> None:
        """Drain spans into ``sink`` (keeping bounded copies for the
        Chrome artifact) — called from the loop's ``telemetry_drain``
        part."""
        spans = self.tracer.drain()
        self._keep(spans)
        for s in spans:
            if s.step is None and step is not None:
                s = dataclasses.replace(s, step=step)
            sink.emit(s.to_event())

    def close(self, sink=None) -> Optional[str]:
        if sink is not None:
            self.flush(sink)
        else:
            self._keep(self.tracer.drain())
        if self.capture is not None:
            self.capture.close()
        if self._session_dropped:
            logger.warning(
                "chrome artifact truncated: %d span(s) beyond the "
                "%d-span session cap (the JSONL event log is the "
                "complete record)", self._session_dropped,
                self._max_spans)
        if self.directory is None:
            return None
        path = os.path.join(self.directory, "trace.chrome.json")
        try:
            os.makedirs(self.directory, exist_ok=True)
            return write_chrome_trace(
                path, self.tracer.chrome_trace(self._all_spans))
        except OSError as e:
            logger.warning("chrome trace write failed: %s",
                           str(e)[:160])
            return None


# ---------------------------------------------------------------------------
# Trace-smoke checker (tools/ci.sh step 8)
# ---------------------------------------------------------------------------

def check_trace(jsonl_path: str, chrome_path: Optional[str] = None, *,
                tolerance: float = 0.02,
                scan_k: Optional[int] = None,
                steps: Optional[int] = None) -> List[str]:
    """Validate a traced run: canonical spans present, every
    ``step_waterfall`` row's parts sum to ``wall_ms`` within
    ``tolerance``, and (when given) the Chrome artifact parses and
    carries both host spans and the canonical step parts.  Returns a
    list of failure strings (empty = pass).

    Scan mode (``scan_k``): the run used the batched-step driver, so
    each waterfall row covers one K-step window — every row must carry
    ``scan_k`` (== ``scan_k`` except a trailing remainder window), and
    with ``steps`` also given there must be exactly ``ceil(steps /
    scan_k)`` rows whose ``scan_k`` values sum to ``steps``.  The
    parts-sum-to-wall invariant is checked per window exactly as per
    step — amortizing dispatch must not break the attribution
    identity."""
    from .summary import load_events

    failures: List[str] = []
    events, malformed = load_events(jsonl_path)
    if malformed:
        failures.append(f"{malformed} malformed line(s) in {jsonl_path}")
    span_names = {e.name for e in events if e.kind == "span"}
    missing = [p for p in WATERFALL_PARTS if p not in span_names]
    if missing:
        failures.append(f"canonical span(s) missing from the event "
                        f"log: {missing}")
    rows = [e for e in events
            if e.kind == "attr" and e.name == "step_waterfall"]
    if not rows:
        failures.append("no step_waterfall attribution rows")
    for e in rows:
        wall = float(e.value)
        parts = sum(float(v) for k, v in e.attrs.items()
                    if k.endswith("_ms") and isinstance(v, (int, float)))
        if wall > 0 and abs(parts - wall) > tolerance * wall:
            failures.append(
                f"step {e.step}: parts sum {parts:.4f} ms != wall "
                f"{wall:.4f} ms (> {tolerance:.0%})")
    if scan_k is not None:
        ks = [e.attrs.get("scan_k") for e in rows]
        bad = [e.step for e, k in zip(rows, ks)
               if not isinstance(k, int)]
        if bad:
            failures.append(f"scan mode: waterfall row(s) at step(s) "
                            f"{bad} carry no scan_k window size")
        else:
            over = [e.step for e, k in zip(rows, ks) if k > scan_k]
            if over:
                failures.append(
                    f"scan mode: row(s) at step(s) {over} cover more "
                    f"than K={scan_k} steps")
            if steps is not None:
                want_rows = -(-steps // scan_k)  # ceil
                if len(rows) != want_rows or sum(ks) != steps:
                    failures.append(
                        f"scan mode: {len(rows)} window row(s) "
                        f"covering {sum(ks)} step(s) != ceil({steps}/"
                        f"{scan_k}) = {want_rows} windows / {steps} "
                        f"steps")
    if chrome_path is not None:
        try:
            with open(chrome_path) as f:
                trace = json.load(f)
            evs = trace.get("traceEvents", [])
            host = [t for t in evs if t.get("ph") == "X"]
            if not host:
                failures.append(f"{chrome_path}: no complete (X) "
                                "events")
            names = {t.get("name") for t in host}
            miss = [p for p in WATERFALL_PARTS if p not in names]
            if miss:
                failures.append(f"{chrome_path}: canonical part "
                                f"span(s) missing: {miss}")
        except (OSError, json.JSONDecodeError) as e:
            failures.append(f"{chrome_path}: unreadable Chrome trace "
                            f"({e})")
    return failures


def main(argv=None) -> int:
    """CLI: ``python -m apex_tpu.monitor.tracing --check RUN.jsonl
    [--chrome TRACE.json]`` — the CI trace-smoke assertion."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.monitor.tracing",
        description="Validate a traced run's event log and Chrome "
                    "artifact (ci.sh trace smoke).")
    ap.add_argument("jsonl", nargs="+",
                    help="monitor JSONL from a --trace run; with "
                         "--serve, several per-replica fleet logs "
                         "merge into one aggregate check")
    ap.add_argument("--chrome", default=None,
                    help="Chrome trace artifact to validate")
    ap.add_argument("--check", action="store_true",
                    help="(default action) run the validations")
    ap.add_argument("--tolerance", type=float, default=0.02,
                    help="parts-sum-to-wall tolerance (default 0.02)")
    ap.add_argument("--scan-k", type=int, default=None, metavar="K",
                    help="scan-driver run: every waterfall row must be "
                         "a K-step window (parts still sum to wall)")
    ap.add_argument("--steps", type=int, default=None, metavar="N",
                    help="with --scan-k: require ceil(N/K) window "
                         "rows covering exactly N steps")
    ap.add_argument("--serve", action="store_true",
                    help="serving-run mode: validate the per-request "
                         "lifecycle chains (every submitted rid ends "
                         "in exactly one terminal event, TTFT present "
                         "for every non-preempted rid, "
                         "queued+prefill+decode sums to the request "
                         "wall), engine gauges, and the per-request "
                         "Chrome lanes instead of the train-loop "
                         "waterfall")
    args = ap.parse_args(argv)
    if args.serve:
        failures = check_serve_trace(args.jsonl, args.chrome,
                                     tolerance=args.tolerance)
    else:
        if len(args.jsonl) > 1:
            ap.error("multiple JSONL paths are the --serve fleet "
                     "form; the waterfall check takes one run log")
        failures = check_trace(args.jsonl[0], args.chrome,
                               tolerance=args.tolerance,
                               scan_k=args.scan_k, steps=args.steps)
    for f in failures:
        print(f"[trace-check] FAIL: {f}", file=sys.stderr)
    if failures:
        return 1
    if args.serve:
        label = args.jsonl[0] if len(args.jsonl) == 1 \
            else f"{len(args.jsonl)} replica logs"
        print(f"[trace-check] OK: {label} "
              "carries complete request lifecycle chains"
              + (f"; {args.chrome} carries the per-request lanes"
                 if args.chrome else ""))
        return 0
    print(f"[trace-check] OK: {args.jsonl[0]} carries the canonical "
          "waterfall"
          + (f" ({-(-args.steps // args.scan_k)} K={args.scan_k} "
             "window(s))" if args.scan_k and args.steps else "")
          + (f"; {args.chrome} parses" if args.chrome else ""))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
