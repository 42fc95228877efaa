"""Telemetry events and sinks — the base layer of :mod:`apex_tpu.monitor`.

One frozen :class:`Event` record and pluggable :class:`Sink` targets.
The reference ships run observability as disconnected fragments (pyprof's
nvtx->parse->prof pipeline, Megatron ``Timers``, ad-hoc
``print_rank_last`` loss lines); every emitter here — step metrics, amp
scale transitions, watchdog alarms, pipeline phase timers, serving
request lifecycles — flows through the same record type into the same
sink, so a killed or stalled run leaves one inspectable log instead of
scattered prints.

:class:`JsonlSink` is crash-safe *by construction*: append-only, one
event per line, flushed per event — every committed line is valid JSON
on its own and there is no end-of-run rewrite to lose (the failure mode
that twice clobbered a results file rewritten whole at the end of a run).
"""
from __future__ import annotations

import dataclasses
import json
import math
import threading
import time
from typing import Any, Dict, List, Mapping, Optional

SCHEMA_VERSION = 1

#: Canonical ``Event.kind`` values (open set — consumers must tolerate
#: unknown kinds):
#:   ``run``     run lifecycle (``run_start`` / ``run_end``)
#:   ``metric``  per-step scalars (loss, grad_norm, lr, step_ms,
#:               tokens_per_sec, mfu, ...)
#:   ``scale``   amp loss-scale state (``loss_scale``, ``overflow``)
#:   ``alarm``   watchdog alarms (``stall``, ``nonfinite_loss``,
#:               ``overflow_streak``) and their ``*_recovered`` pairs
#:   ``timer``   phase times exported from ``Timers.events`` (seconds)
#:   ``span``    host spans from :mod:`apex_tpu.monitor.tracing`
#:               (value = duration seconds; ``attrs.t0``/``tid``/
#:               ``depth`` reconstruct the Chrome timeline)
#:   ``attr``    per-step wall-time attribution rows
#:               (``step_waterfall``: value = wall ms, attrs carry the
#:               per-component ms + ``wall_device_ratio``)
#:   ``trace``   on-demand capture lifecycle (``capture_requested`` /
#:               ``capture_started`` / ``capture_stopped``)
#:   ``resilience`` preemption / restart / checkpoint-integrity
#:               lifecycle (``termination_requested``, ``clean_exit``,
#:               ``run_resumed``, ``preempt_exit``, ``attempt_start`` /
#:               ``attempt_error`` / ``attempt_backoff`` /
#:               ``attempt_done`` / ``run_giveup``,
#:               ``escalation_abort``, ``ckpt_skipped`` / ``ckpt_gc``)
#:   ``telemetry`` deferred-telemetry drain bookkeeping
#:               (``telemetry_drain``: rows emitted + drain ordinal)
#:   ``serving`` request lifecycle + engine events from
#:               :mod:`apex_tpu.serving` (``request_submitted`` /
#:               ``request_rejected`` / ``request_admitted`` /
#:               ``request_first_token`` / ``request_done``,
#:               ``decode_step``, ``serve_compile``, ``serve_preempt``,
#:               ``serve_done``, ``engine_snapshot``, ``weights_held``
#:               / ``weights_swapped``; resilience:
#:               ``deadline_exceeded``, ``request_shed``,
#:               ``request_replayed``, ``journal_replay``,
#:               ``crash_reset``, ``alloc_rejected``,
#:               ``escalation_drain`` — ``request_done`` carries a
#:               ``terminal`` reason on every path)
#:   ``journal``  serving request-journal records
#:               (serving/resilience.RequestJournal: ``submit`` /
#:               ``progress`` / ``terminal`` / ``replay`` — its OWN
#:               JSONL file, not the run log)
#:   ``serve_tick`` per-tick engine gauges (batch / bucket shape /
#:               free+reserved blocks / queue depth / admissions+
#:               evictions+preemptions this window — the fleet-router
#:               feed, cadence ``APEX_TPU_SERVE_TICK_EVERY``)
#:   ``fleet_tick`` per-router-round fleet aggregation
#:               (:class:`apex_tpu.monitor.export.FleetAggregator`:
#:               summed queue depth / free-blocks-net / backlog, token
#:               and compile deltas over MEASURED per-replica engine
#:               ticks — the ``ticks`` attr is the rate denominator,
#:               never the nominal cadence — plus slope/EWMA trends)
#:   ``slo``      SLO bookkeeping from :mod:`apex_tpu.serving.metrics`
#:               (``slo_objectives`` — the objective definitions every
#:               ``slo_burn`` alarm must trace back to — and
#:               ``slo_recovered`` episode-clear records; the burn
#:               itself is kind ``alarm`` name ``slo_burn``, routed
#:               through the watchdog so escalation hooks see it)
#:   ``metrics``  exporter lifecycle (``metrics_server_started`` /
#:               ``metrics_server_stopped`` — trace_check pairs them)
KINDS = ("run", "metric", "scale", "alarm", "timer", "span", "attr",
         "trace", "resilience", "telemetry", "serving",
         "serve_tick", "fleet_tick", "slo", "metrics")


def _jsonable(v: Any) -> Any:
    """Coerce device scalars / numpy types to plain JSON values.
    Mappings and sequences recurse, so a structured attr (the serving
    ``engine_snapshot`` request list, rejection-reason counts) lands
    as real JSON instead of a ``str()`` blob."""
    if v is None or isinstance(v, (bool, int, str)):
        return v
    if isinstance(v, float):
        # bare NaN/Infinity is not valid JSON; encode as a string so
        # every committed line parses everywhere
        return v if math.isfinite(v) else str(v)
    if isinstance(v, Mapping):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    try:
        f = float(v)
        return f if math.isfinite(f) else str(f)
    except (TypeError, ValueError):
        return str(v)


@dataclasses.dataclass(frozen=True)
class Event:
    """One telemetry record.

    ``value`` carries the single scalar most consumers want; anything
    richer rides ``attrs``.  ``time`` is host wall-clock (epoch
    seconds); ``step`` is the training step, ``None`` for run-level
    events.
    """

    time: float
    step: Optional[int]
    kind: str
    name: str
    value: Optional[float] = None
    attrs: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        d: Dict[str, Any] = {
            "time": round(float(self.time), 6),
            "step": None if self.step is None else int(self.step),
            "kind": self.kind,
            "name": self.name,
            "value": _jsonable(self.value),
        }
        if self.attrs:
            d["attrs"] = {str(k): _jsonable(v)
                          for k, v in self.attrs.items()}
        return json.dumps(d, separators=(",", ":"))

    @staticmethod
    def from_json(line: str) -> "Event":
        d = json.loads(line)
        return Event(time=float(d["time"]),
                     step=d.get("step"),
                     kind=d["kind"],
                     name=d["name"],
                     value=d.get("value"),
                     attrs=d.get("attrs") or {})


def terminal_reason(attrs: Mapping[str, Any]) -> str:
    """The terminal reason of a serving ``request_done`` event's
    attrs: the ``terminal`` attr when present (finished / preempted /
    deadline / deadline_exceeded / shed), else the pre-ISSUE-13
    fallback on the ``preempted`` flag — ONE implementation shared by
    every consumer (summary digest, ``trace_check --serve``) so they
    cannot disagree about the same event."""
    return str(attrs.get("terminal")
               or ("preempted" if attrs.get("preempted")
                   else "finished"))


def emit_resilience(sink, name: str, *, value=None,
                    step: Optional[int] = None, clock=time.time,
                    **attrs) -> None:
    """Emit one ``resilience``-kind event into ``sink`` (no-op when
    ``sink`` is None) — the single construction point shared by
    :mod:`apex_tpu.resilience` and the checkpoint-integrity layer, so
    the record shape cannot drift between emitters."""
    if sink is None:
        return
    sink.emit(Event(time=clock(), step=step, kind="resilience",
                    name=name, value=value, attrs=attrs))


# ---------------------------------------------------------------------------
# Sinks
# ---------------------------------------------------------------------------

class Sink:
    """Where events go.  Implementations must be cheap per event and
    must never raise out of ``emit`` into the training loop."""

    def emit(self, event: Event) -> None:
        raise NotImplementedError

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class MemorySink(Sink):
    """In-process event list — the test double."""

    def __init__(self):
        self.events: List[Event] = []

    def emit(self, event: Event) -> None:
        self.events.append(event)

    def by_kind(self, kind: str) -> List[Event]:
        return [e for e in self.events if e.kind == kind]

    def by_name(self, name: str) -> List[Event]:
        return [e for e in self.events if e.name == name]


class JsonlSink(Sink):
    """Append-only JSONL file, one event per line, flushed per line.

    Crash-safe by construction: a kill at any instant leaves a file
    whose every complete line is independently valid JSON (at worst one
    truncated trailing line, which :func:`~apex_tpu.monitor.summary.
    load_events` tolerates).  There is deliberately no buffering and no
    end-of-run rewrite.
    """

    def __init__(self, path: str, append: bool = True):
        self.path = path
        self._f = open(path, "a" if append else "w")
        self._lock = threading.Lock()

    def emit(self, event: Event) -> None:
        line = event.to_json()
        with self._lock:
            if self._f is None:
                return
            self._f.write(line + "\n")
            self._f.flush()

    def flush(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.flush()

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


class TeeSink(Sink):
    """Fan one event stream out to several sinks."""

    def __init__(self, *sinks: Sink):
        self.sinks = list(sinks)

    def emit(self, event: Event) -> None:
        for s in self.sinks:
            s.emit(event)

    def flush(self) -> None:
        for s in self.sinks:
            s.flush()

    def close(self) -> None:
        for s in self.sinks:
            s.close()


class WriterSink(Sink):
    """Adapter: forward scalar-valued events to any TensorBoard-like
    object exposing ``add_scalar(tag, value, global_step)`` — an
    existing summary writer plugs into the monitor unchanged."""

    def __init__(self, writer: Any):
        self.writer = writer

    def emit(self, event: Event) -> None:
        if event.value is None or isinstance(event.value, str):
            return
        self.writer.add_scalar(f"{event.kind}/{event.name}",
                               float(event.value),
                               0 if event.step is None else event.step)


class BackgroundThreadError(RuntimeError):
    """A background thread died with an uncaught exception — surfaced
    by :class:`ThreadExceptionCapture` instead of vanishing into
    stderr."""


class ThreadExceptionCapture:
    """``threading.excepthook`` wiring: an uncaught exception in a
    background thread becomes a terminal ``run_error`` monitor event
    and a raisable failure, instead of a traceback on stderr and a
    silently dead thread (the default — a crashed watchdog heartbeat
    or fleet replica thread used to leave no machine-readable record
    and fail no test).

    ``target`` is anything with either the ``StepMonitor.event``
    signature or the :class:`Sink` ``emit`` one (or ``None``: record
    only — the conftest fixture reads ``failures`` at teardown).  The
    hook appends one record per crash (a single list append — no
    torn state to lock) and, with ``chain=True`` (the default),
    chains to the previously installed hook so the stderr traceback
    is not lost (``chain=False`` swallows it — for tests that crash
    threads on purpose and assert on the capture).  ``raise_first()``
    re-raises the first crash wrapped in
    :class:`BackgroundThreadError`; call it after join/teardown so a
    run whose main loop succeeded still fails when a thread it owned
    died.
    """

    def __init__(self, target: Any = None, *, clock=time.time,
                 chain: bool = True,
                 attrs: Optional[Dict[str, Any]] = None):
        self._target = target
        self._clock = clock
        self._chain = bool(chain)
        # merged into every emitted run_error's attrs — e.g. the
        # fleet driver stamps replica="fleet" so a crash logged
        # through one replica's sink is not misattributed to it
        self._attrs = dict(attrs or {})
        self._prev = None
        self._installed = False
        self.failures: List[Dict[str, Any]] = []

    def install(self) -> "ThreadExceptionCapture":
        if self._installed:
            return self
        self._prev = threading.excepthook
        threading.excepthook = self._hook
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        threading.excepthook = self._prev
        self._prev = None
        self._installed = False

    def _hook(self, args) -> None:
        record = {
            "thread": getattr(args.thread, "name", None) or "?",
            "error": getattr(args.exc_type, "__name__",
                             str(args.exc_type)),
            "message": str(args.exc_value)[:200],
            "background": True,
            "exception": args.exc_value,
        }
        self.failures.append(record)
        try:
            self._emit(record)
        except Exception:  # apex-lint: disable=APX202 -- the hook runs on a dying thread; a sink failure here must not mask the original crash (recorded above)
            pass
        if self._chain:
            prev = self._prev or threading.__excepthook__
            prev(args)

    def _emit(self, record: Dict[str, Any]) -> None:
        t = self._target
        if t is None:
            return
        attrs = {k: v for k, v in record.items() if k != "exception"}
        attrs.update(self._attrs)
        ev = getattr(t, "event", None)
        if callable(ev):
            ev("run", "run_error", **attrs)
        else:
            t.emit(Event(time=self._clock(), step=None, kind="run",
                         name="run_error", attrs=attrs))

    def raise_first(self) -> None:
        """Raise :class:`BackgroundThreadError` for the first captured
        crash (no-op when every thread exited clean)."""
        if not self.failures:
            return
        rec = self.failures[0]
        raise BackgroundThreadError(
            f"background thread {rec['thread']!r} died: "
            f"{rec['error']}: {rec['message']}"
            + (f" (+{len(self.failures) - 1} more)"
               if len(self.failures) > 1 else "")
        ) from rec.get("exception")

    def __enter__(self) -> "ThreadExceptionCapture":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


class ScalarWriter:
    """The inverse adapter: an ``add_scalar``-style facade over a sink,
    so ``Timers.write(names, writer, iteration)``
    (apex_tpu/transformer/pipeline_parallel/utils.py) and any other
    add_scalar caller emits :class:`Event` s without modification."""

    def __init__(self, sink: Sink, kind: str = "timer",
                 clock=time.time):
        self.sink = sink
        self.kind = kind
        self._clock = clock

    def add_scalar(self, name: str, value: float,
                   global_step: Optional[int] = None) -> None:
        self.sink.emit(Event(time=self._clock(),
                             step=None if global_step is None
                             else int(global_step),
                             kind=self.kind, name=str(name),
                             value=float(value)))
