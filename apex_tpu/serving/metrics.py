"""Per-request serving telemetry: request lifecycle, engine gauges,
and the on-demand engine snapshot.

PR 9's :class:`~apex_tpu.serving.engine.ServeSummary` reports lifetime
totals — a request that waited 800 ms in the admission queue and one
admitted instantly are indistinguishable.  This module gives the
engine the Orca/vLLM serving vocabulary (queue wait, time-to-first-
token, inter-token latency) with the same sync-free discipline as the
PR-7 tracer: every number here is host bookkeeping the engine already
holds, so the one-fetch-per-tick budget and the zero-recompile
contract are untouched.  Three pieces:

* :class:`RequestTrace` / :class:`ServeMetrics` — every request emits
  a monotonic lifecycle chain through the monitor sinks
  (``request_submitted → request_admitted → request_first_token →
  request_done``; a rejected submit emits ``request_rejected``
  instead, and a drained request ends in ``request_done`` with
  ``preempted=true``), each event stamped with host wall time and the
  engine tick index.  The terminal event carries the whole per-request
  timing breakdown (``queue_wait_ms + prefill_ms + decode_ms ==
  wall_ms`` by construction, from one clock), from which the summary
  derives queue-wait / TTFT / ITL / decode-tokens-per-sec
  distributions over a bounded window, and from which the Chrome
  export (:func:`apex_tpu.monitor.tracing.serve_lanes_from_events`)
  rebuilds one Perfetto lane per request with queued/prefill/decode
  phases.
* :class:`EngineGauges` — one ``kind="serve_tick"`` event per engine
  tick (or every K ticks, ``APEX_TPU_SERVE_TICK_EVERY``): running
  batch, active bucket shape, free/reserved blocks, queue depth,
  admissions/evictions/preemptions this window, compile count — the
  feed a fleet router load-balances on (ROADMAP item 1).
* :class:`SnapshotTrigger` — file-touch or SIGUSR1 dumps the live
  engine state as ONE ``engine_snapshot`` JSON event at the next tick
  boundary (exactly one per trigger; the same flag-only-handler
  discipline as :class:`~apex_tpu.monitor.tracing.CaptureTrigger`) —
  the wedged-serve post-mortem hook.

All clocks are injectable (fake-clock tests in
tests/test_serving_metrics.py); the read side — ``monitor_summary``'s
serving section and ``tools/trace_check.py --serve`` — lives in
:mod:`apex_tpu.monitor`.  Worked example: docs/api/serving.md.
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from ..analysis.flags import flag_float, flag_int, flag_str
from ..monitor.summary import _pct
from ..monitor.tracing import serve_chrome_trace
from ..utils.log_util import get_logger

logger = get_logger(__name__)

__all__ = ["RequestTrace", "ServeMetrics", "EngineGauges",
           "ReplicaMonitor", "SnapshotTrigger", "SLObjective",
           "SLOTracker"]

# distribution samples kept per series (queue-wait / ttft / itl /
# per-request decode tok/s) — same bound as the engine's per-token
# latency window, so a weeks-long serve keeps host memory flat
_SAMPLE_WINDOW = 100_000
# completed RequestTrace records kept for the Chrome lane export (the
# JSONL event log is the complete record; the in-memory list backs
# the artifact a driver writes at close)
_TRACE_WINDOW = 10_000


@dataclasses.dataclass
class RequestTrace:
    """One request's lifecycle timestamps, all on the engine clock.

    The phase boundaries are shared instants — queue wait ends exactly
    where prefill starts, prefill where decode starts — so
    ``queue_wait_s + prefill_s + decode_s == wall_s`` holds by
    construction (the 2% tolerance in the checkers covers float
    rounding of the exported milliseconds, nothing else)."""

    rid: str
    prompt_len: int
    submit_t: float
    submit_tick: int
    admit_t: Optional[float] = None
    admit_tick: Optional[int] = None
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None
    done_tick: Optional[int] = None
    done_wall: Optional[float] = None   # epoch seconds (Chrome anchor)
    new_tokens: int = 0
    preempted: bool = False
    # terminal reason (ISSUE-13): finished | preempted | deadline |
    # deadline_exceeded | shed — the lifecycle chains' new terminal
    # paths all close through request_done, just with a reason
    terminal: str = "finished"

    @property
    def admitted(self) -> bool:
        return self.admit_t is not None

    @property
    def queue_wait_s(self) -> float:
        """Submit → admission start (for a never-admitted request the
        whole wall was queue wait)."""
        end = self.admit_t if self.admitted else self.done_t
        return max(0.0, (end or self.submit_t) - self.submit_t)

    @property
    def prefill_s(self) -> float:
        """Admission start → first token.  A request preempted while
        its (possibly chunked) prefill was still running has no first
        token: its whole post-admission wall counts as prefill, so
        the parts still sum to the wall."""
        if not self.admitted:
            return 0.0
        end = self.first_token_t if self.first_token_t is not None \
            else (self.done_t if self.done_t is not None
                  else self.admit_t)
        return max(0.0, end - self.admit_t)

    @property
    def decode_s(self) -> float:
        if not self.admitted or self.done_t is None \
                or self.first_token_t is None:
            return 0.0
        return max(0.0, self.done_t - self.first_token_t)

    @property
    def wall_s(self) -> float:
        return max(0.0, (self.done_t or self.submit_t) - self.submit_t)

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit → first generated token (the prefill output token);
        None for a request preempted before admission or before its
        chunked prefill produced the token."""
        if not self.admitted or self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def decode_tokens_per_sec(self) -> Optional[float]:
        """Steady-state decode rate (tokens after the first over the
        decode span); None until >= 2 tokens exist."""
        if self.new_tokens < 2 or self.decode_s <= 0.0:
            return None
        return (self.new_tokens - 1) / self.decode_s

    def lane_row(self) -> Dict[str, Any]:
        """The Chrome-lane row shape
        :func:`apex_tpu.monitor.tracing.serve_lane_events` consumes."""
        return {
            "rid": self.rid,
            "end": self.done_wall,
            "queue_wait_ms": self.queue_wait_s * 1e3,
            "prefill_ms": self.prefill_s * 1e3 if self.admitted
            else None,
            "decode_ms": self.decode_s * 1e3 if self.admitted
            else None,
            "new_tokens": self.new_tokens,
            "preempted": self.preempted,
            "terminal": self.terminal,
            "tick": self.done_tick,
        }


def _percentile(xs, q: float) -> Optional[float]:
    """Empty-tolerant facade over the summary renderer's
    linear-interpolation percentile (one implementation of the math,
    same method as np.percentile's default — the engine's latency
    series and these stay comparable)."""
    s = list(xs)
    if not s:
        return None
    return float(_pct(s, q))


class EngineGauges:
    """Tick-gauge accumulator + cadence: the engine reports every tick,
    one ``serve_tick`` event leaves every ``every`` ticks (counters —
    admissions/evictions/preemptions/compiles — accumulate across the
    window; level gauges — batch, buckets, pool, queue — carry the
    window's last tick).  A trailing partial window flushes at run
    end, so the final engine state is always in the log."""

    def __init__(self, every: int = 1):
        self.every = max(1, int(every))
        self.emitted = 0
        self.used_blocks_hw = 0
        self.shared_blocks_hw = 0
        self._ticks = 0
        self._admitted = 0
        self._warm_admitted = 0
        self._finished = 0
        self._preempted = 0
        self._shed = 0
        self._deadline = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._compiles_seen = 0
        self._last: Optional[Dict[str, Any]] = None

    def on_admit(self, warm: bool = False) -> None:
        self._admitted += 1
        if warm:
            self._warm_admitted += 1

    def on_spec(self, proposed: int, accepted: int) -> None:
        """One speculative tick's draft bookkeeping: ``proposed``
        draft tokens scored, ``accepted`` kept by the greedy match —
        the window's acceptance feed (``spec_accept_rate`` on the
        rolled gauge event)."""
        self._spec_proposed += int(proposed)
        self._spec_accepted += int(accepted)

    def on_finish(self, terminal="finished", *,
                  preempted: Optional[bool] = None) -> None:
        """One terminal request this window.  ``terminal`` is the
        reason string; the pre-ISSUE-13 signature (a bool, positional
        or as the ``preempted`` keyword) still works."""
        if preempted is not None:
            terminal = "preempted" if preempted else "finished"
        elif isinstance(terminal, bool):
            terminal = "preempted" if terminal else "finished"
        if terminal == "finished":
            self._finished += 1
        elif terminal == "preempted":
            self._preempted += 1
        elif terminal == "shed":
            self._shed += 1
        else:                       # deadline / deadline_exceeded
            self._deadline += 1

    def observe(self, tick: int, **levels) -> Optional[Dict[str, Any]]:
        """Record one engine tick's level gauges; returns the event
        attrs when the cadence says this tick emits, else None."""
        self._ticks += 1
        self.used_blocks_hw = max(self.used_blocks_hw,
                                  int(levels.get("used_blocks", 0)))
        self.shared_blocks_hw = max(self.shared_blocks_hw,
                                    int(levels.get("shared_blocks",
                                                   0)))
        self._last = dict(levels, last_tick=tick)
        if self._ticks >= self.every:
            return self._roll()
        return None

    def router_snapshot(self) -> Dict[str, Any]:
        """The last observed tick's level gauges plus the high-water
        counters, WITHOUT advancing the cadence window — the cheap
        read a fleet router polls between its own dispatch rounds
        (:meth:`~apex_tpu.serving.engine.ServingEngine.
        router_snapshot` composes this with the pool's live state)."""
        snap = dict(self._last or {})
        snap["used_blocks_high_water"] = self.used_blocks_hw
        snap["shared_blocks_high_water"] = self.shared_blocks_hw
        return snap

    def flush(self) -> Optional[Dict[str, Any]]:
        """Close a trailing partial window (None when nothing is
        pending).  A window may hold counters but zero ticks: the
        run's final evictions happen in a tick that decodes nothing,
        so the flush is how they reach the log."""
        if self._ticks == 0 and not (self._admitted or self._finished
                                     or self._preempted or self._shed
                                     or self._deadline
                                     or self._spec_proposed):
            return None
        return self._roll()

    def _roll(self) -> Dict[str, Any]:
        attrs = dict(self._last or {})
        compiles = int(attrs.get("compiles", self._compiles_seen))
        attrs.update(
            ticks=self._ticks,
            admitted=self._admitted,
            warm_admitted=self._warm_admitted,
            finished=self._finished,
            preempted=self._preempted,
            new_compiles=compiles - self._compiles_seen,
            used_blocks_high_water=self.used_blocks_hw,
        )
        if self._shed:
            attrs["shed"] = self._shed
        if self._deadline:
            attrs["deadline_exceeded"] = self._deadline
        if self.shared_blocks_hw:
            attrs["shared_blocks_high_water"] = self.shared_blocks_hw
        if self._spec_proposed:
            attrs["spec_proposed"] = self._spec_proposed
            attrs["spec_accepted"] = self._spec_accepted
            attrs["spec_accept_rate"] = round(
                self._spec_accepted / self._spec_proposed, 4)
        self._compiles_seen = compiles
        self._ticks = 0
        self._admitted = self._warm_admitted = 0
        self._finished = self._preempted = 0
        self._shed = self._deadline = 0
        self._spec_proposed = self._spec_accepted = 0
        self.emitted += 1
        return attrs


class ReplicaMonitor:
    """Monitor facade stamping ``replica=<id>`` on every event.

    A fleet's replicas may share one JSONL sink or write one file
    each; either way every event a replica's engine, metrics layer, or
    supervisor emits must carry a stable replica id so the aggregation
    side (``monitor_summary`` fleet digest, ``trace_check --serve``
    over per-replica logs) can attribute chains without parsing rids.
    Wraps anything with the ``StepMonitor.event`` signature; every
    other attribute (``watchdog``, ``close``, sinks) passes through,
    so the engine's heartbeat and teardown paths see the real
    monitor.  An explicit ``replica=`` in an event's attrs wins — the
    stamp is a default, not an override."""

    def __init__(self, monitor, replica_id: str):
        self._monitor = monitor
        self.replica_id = str(replica_id)

    def event(self, kind: str, name: str, value=None, **attrs) -> None:
        attrs.setdefault("replica", self.replica_id)
        self._monitor.event(kind, name, value=value, **attrs)

    def __getattr__(self, name):
        return getattr(self._monitor, name)


# ---------------------------------------------------------------------------
# Per-priority-class SLOs with multi-window burn-rate alerting
# ---------------------------------------------------------------------------

# a p99 latency objective budgets 1% violations by definition
_P99_BUDGET = 0.01
# terminals the availability objective counts as bad: the engine
# failed the request (shed under pressure, or past its deadline).
# preempted is NOT bad — a clean drain is operator-initiated.
_UNAVAILABLE_TERMINALS = ("shed", "deadline", "deadline_exceeded")


@dataclasses.dataclass(frozen=True)
class SLObjective:
    """One priority class's declarative objectives (0 disables a
    dimension).  ``priority_class`` is ``"p<priority>"`` matching
    :class:`~apex_tpu.serving.engine.Request.priority`, or ``"*"``
    for one class-agnostic objective over all traffic (what the
    ``APEX_TPU_SLO_*`` flags build).  ``availability`` is the target
    good fraction (e.g. 0.99): a request is *bad* when its terminal
    is shed / deadline / deadline_exceeded — the non-shed/non-
    deadline fraction must stay above the target."""

    priority_class: str = "*"
    ttft_p99_ms: float = 0.0
    itl_p99_ms: float = 0.0
    availability: float = 0.0

    def matches(self, cls: str) -> bool:
        return self.priority_class in ("*", cls)

    def dimensions(self):
        """``(dimension, threshold, error budget)`` triples for the
        enabled dimensions."""
        if self.ttft_p99_ms > 0:
            yield "ttft", self.ttft_p99_ms, _P99_BUDGET
        if self.itl_p99_ms > 0:
            yield "itl", self.itl_p99_ms, _P99_BUDGET
        if self.availability > 0:
            yield ("availability", self.availability,
                   max(1e-9, 1.0 - self.availability))

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class SLOTracker:
    """Multi-window burn-rate alerting over declarative objectives.

    The SRE recipe, tick-denominated: each enabled (objective,
    dimension) pair keeps a bounded deque of ``(tick, bad)`` samples;
    :meth:`evaluate` computes the burn rate — bad fraction over the
    error budget — over a fast window (~1 min equivalent in engine
    ticks) and a slow window (~1 hr equivalent) and trips when BOTH
    exceed ``burn_threshold`` (a fast blip alone or a long-decayed
    stain alone never pages).  Episodes latch: one ``burn``
    transition when the condition first holds, one ``recovered`` when
    the fast window drops back under — the watchdog's once-per-
    episode discipline, enforced here so the alarm machinery stays a
    pass-through.  Everything is driven by the engine tick (injected,
    fake-clock tests in tests/test_serving_slo.py) and touched only
    from the engine thread — no locks.

    Feeds: :class:`ServeMetrics` records TTFT/ITL samples and
    terminal availability per priority class; the engine calls
    :meth:`evaluate` once per tick from its telemetry boundary and
    routes ``burn`` transitions through the watchdog
    (:meth:`~apex_tpu.monitor.watchdog.Watchdog.alarm`) so the
    escalation hook sees them like any other alarm."""

    def __init__(self, objectives: "List[SLObjective]", *,
                 fast_window: int = 64, slow_window: int = 1024,
                 burn_threshold: float = 2.0):
        self.objectives = [o for o in objectives
                           if any(True for _ in o.dimensions())]
        self.fast_window = max(1, int(fast_window))
        self.slow_window = max(self.fast_window, int(slow_window))
        self.burn_threshold = float(burn_threshold)
        # (objective idx, dimension) -> deque[(tick, bad)]
        self._samples: Dict[tuple, deque] = {}
        # latched episodes: key -> attrs of the burn that opened it
        self._burning: Dict[tuple, Dict[str, Any]] = {}
        self.episodes = 0
        self.recoveries = 0

    @classmethod
    def from_flags(cls) -> "Optional[SLOTracker]":
        """One class-agnostic objective from the ``APEX_TPU_SLO_*``
        flags; None when every dimension is disabled (the default —
        no tracker, no per-tick evaluation cost)."""
        obj = SLObjective(
            priority_class="*",
            ttft_p99_ms=flag_float("APEX_TPU_SLO_TTFT_P99_MS"),
            itl_p99_ms=flag_float("APEX_TPU_SLO_ITL_P99_MS"),
            availability=flag_float("APEX_TPU_SLO_AVAILABILITY"))
        if not any(True for _ in obj.dimensions()):
            return None
        return cls([obj])

    @property
    def enabled(self) -> bool:
        return bool(self.objectives)

    # -- sample feeds (called by ServeMetrics) ---------------------------

    def _record(self, dimension: str, cls_name: str, bad: bool,
                tick: int) -> None:
        for i, obj in enumerate(self.objectives):
            if not obj.matches(cls_name):
                continue
            if not any(d == dimension for d, _, _ in
                       obj.dimensions()):
                continue
            dq = self._samples.setdefault((i, dimension), deque())
            dq.append((int(tick), 1 if bad else 0))

    def record_ttft(self, cls_name: str, ttft_ms: float,
                    tick: int) -> None:
        for i, obj in enumerate(self.objectives):
            if obj.matches(cls_name) and obj.ttft_p99_ms > 0:
                dq = self._samples.setdefault((i, "ttft"), deque())
                dq.append((int(tick),
                           1 if ttft_ms > obj.ttft_p99_ms else 0))

    def record_itl(self, cls_name: str, itl_ms: float,
                   tick: int) -> None:
        for i, obj in enumerate(self.objectives):
            if obj.matches(cls_name) and obj.itl_p99_ms > 0:
                dq = self._samples.setdefault((i, "itl"), deque())
                dq.append((int(tick),
                           1 if itl_ms > obj.itl_p99_ms else 0))

    def record_terminal(self, cls_name: str, terminal: str,
                        tick: int) -> None:
        bad = terminal in _UNAVAILABLE_TERMINALS
        self._record("availability", cls_name, bad, tick)

    # -- evaluation ------------------------------------------------------

    def _burn(self, dq: deque, tick: int, window: int,
              budget: float) -> "tuple":
        lo = tick - window
        n = bad = 0
        for t, b in dq:
            if t > lo:
                n += 1
                bad += b
        if n == 0:
            return 0.0, 0, 0
        return (bad / n) / budget, n, bad

    def evaluate(self, tick: int) -> "List[Dict[str, Any]]":
        """Advance to ``tick``: evict samples past the slow window,
        recompute every pair's dual-window burn, and return the
        episode TRANSITIONS (``action`` = ``burn`` | ``recovered``)
        — at most one of each per pair per episode, the once-per-
        episode contract the engine forwards to the alarm path."""
        transitions: List[Dict[str, Any]] = []
        for i, obj in enumerate(self.objectives):
            for dimension, threshold, budget in obj.dimensions():
                key = (i, dimension)
                dq = self._samples.get(key)
                if dq is None:
                    continue
                lo = tick - self.slow_window
                while dq and dq[0][0] <= lo:
                    dq.popleft()
                burn_slow, n_slow, bad_slow = self._burn(
                    dq, tick, self.slow_window, budget)
                burn_fast, n_fast, bad_fast = self._burn(
                    dq, tick, self.fast_window, budget)
                attrs = {
                    "priority_class": obj.priority_class,
                    "dimension": dimension,
                    "objective": threshold,
                    "budget": budget,
                    "burn_threshold": self.burn_threshold,
                    "burn_fast": round(burn_fast, 4),
                    "burn_slow": round(burn_slow, 4),
                    "bad_fast": bad_fast, "n_fast": n_fast,
                    "bad_slow": bad_slow, "n_slow": n_slow,
                }
                tripping = (n_fast > 0
                            and burn_fast >= self.burn_threshold
                            and burn_slow >= self.burn_threshold)
                if tripping and key not in self._burning:
                    self._burning[key] = attrs
                    self.episodes += 1
                    transitions.append(dict(attrs, action="burn"))
                elif key in self._burning and not tripping \
                        and burn_fast < self.burn_threshold:
                    del self._burning[key]
                    self.recoveries += 1
                    transitions.append(dict(attrs,
                                            action="recovered"))
        return transitions

    # -- surfaces --------------------------------------------------------

    @property
    def burning(self) -> "List[str]":
        """Active episodes as ``class/dimension`` strings (the
        /healthz payload)."""
        return sorted(
            f"{self.objectives[i].priority_class}/{dim}"
            for i, dim in self._burning)

    def objectives_attrs(self) -> Dict[str, Any]:
        """The objective-definition event payload (``kind="slo"``,
        ``name="slo_objectives"``) — the schema every ``slo_burn``
        must pair with (``trace_check --serve`` asserts it)."""
        return {
            "fast_window": self.fast_window,
            "slow_window": self.slow_window,
            "burn_threshold": self.burn_threshold,
            "objectives": [o.as_dict() for o in self.objectives],
        }

    def summary_attrs(self) -> Dict[str, Any]:
        return {
            "slo_burn_episodes": self.episodes,
            "slo_recoveries": self.recoveries,
            "slo_burning": self.burning,
        }


class ServeMetrics:
    """The engine's request-lifecycle + gauge telemetry layer.

    Owned by :class:`~apex_tpu.serving.engine.ServingEngine`; every
    hook is host-only bookkeeping (clock reads + dict/deque updates)
    and emission goes through the engine's monitor (anything with the
    ``StepMonitor.event`` signature; None records distributions but
    emits nothing).  Timestamps use the engine's
    injectable monotonic clock, wall-anchored once at construction
    (the :class:`~apex_tpu.monitor.tracing.SpanTracer` trick) so
    exported Chrome lanes line up with device traces captured in the
    same process."""

    def __init__(self, *, monitor=None,
                 clock: Callable[[], float] = time.perf_counter,
                 wall_clock: Callable[[], float] = time.time,
                 tick_every: Optional[int] = None,
                 window: int = _SAMPLE_WINDOW,
                 trace_window: int = _TRACE_WINDOW,
                 slo: Optional[SLOTracker] = None):
        self._monitor = monitor
        self._clock = clock
        self._perf0 = clock()
        self._wall0 = wall_clock()
        self.gauges = EngineGauges(
            tick_every if tick_every is not None
            else flag_int("APEX_TPU_SERVE_TICK_EVERY"))
        # optional SLO layer: the lifecycle hooks below feed it
        # per-class samples; the engine evaluates it per tick
        self.slo = slo
        self._open: Dict[str, RequestTrace] = {}
        self.completed: deque = deque(maxlen=trace_window)
        self.rejected: Dict[str, int] = {}
        # lifetime terminal counts by reason — the exporter's
        # requests_total counter source (same on_done hook, no second
        # bookkeeping path)
        self.terminals: Dict[str, int] = {}
        self._queue_wait_ms: deque = deque(maxlen=window)
        self._ttft_ms: deque = deque(maxlen=window)
        self._itl_ms: deque = deque(maxlen=window)
        self._decode_tps: deque = deque(maxlen=window)
        # percentile cache: recomputed only when a series grew (the
        # per-tick exporter publish must not re-sort idle windows);
        # the mark is a monotone append count, not lengths — a
        # saturated bounded deque keeps its length while its contents
        # roll
        self._pct_cache: Optional[Dict[str, Optional[float]]] = None
        self._pct_appends = 0
        self._pct_mark = -1

    @staticmethod
    def priority_class(request) -> str:
        """The SLO bucket a request belongs to: ``p<priority>``."""
        return f"p{int(getattr(request, 'priority', 0) or 0)}"

    # -- emission ------------------------------------------------------------

    def _emit(self, kind: str, name: str, value=None,
              tick: Optional[int] = None, **attrs) -> None:
        if self._monitor is not None:
            self._monitor.event(kind, name, value=value, step=tick,
                                **attrs)

    def _wall_at(self, t: float) -> float:
        return self._wall0 + (t - self._perf0)

    # -- request lifecycle ---------------------------------------------------

    def on_reject(self, rid, reason: str, tick: int) -> None:
        """A submit the engine refused (before it entered the queue)."""
        self.rejected[reason] = self.rejected.get(reason, 0) + 1
        self._emit("serving", "request_rejected", tick=tick,
                   rid=str(rid), reason=reason)

    def on_submit(self, request, tick: int) -> None:
        # the engine stamps request.submit_t just before this hook
        # (respecting a pre-anchored instant — the fleet router's
        # disaggregated submissions); the lifecycle chain must share
        # that anchor or queue-wait/TTFT would silently exclude the
        # pre-engine wait
        t = getattr(request, "submit_t", None)
        if t is None:
            t = self._clock()
        self._open[str(request.rid)] = RequestTrace(
            rid=str(request.rid), prompt_len=len(request.prompt),
            submit_t=t, submit_tick=tick)
        self._emit("serving", "request_submitted", tick=tick,
                   rid=str(request.rid),
                   prompt_len=len(request.prompt))

    def on_admit(self, request, tick: int, admit_t: float,
                 prefill_s: Optional[float] = None, **attrs) -> None:
        """Admission happened: ``admit_t`` is the engine-clock instant
        queue wait ended and prefill began.  With ``prefill_s`` (the
        synchronous whole-prompt path) the first generated token
        exists at ``admit_t + prefill_s`` and both lifecycle events
        emit here; a chunked prefill passes ``prefill_s=None`` and
        reports the token later through :meth:`on_first_token` — TTFT
        is always measured to the REAL first token, however many
        ticks the prefill spans."""
        tr = self._open.get(str(request.rid))
        if tr is None:  # engine-internal admit without a submit record
            tr = RequestTrace(rid=str(request.rid),
                              prompt_len=len(request.prompt),
                              submit_t=admit_t, submit_tick=tick)
            self._open[tr.rid] = tr
        tr.admit_t = admit_t
        tr.admit_tick = tick
        qw_ms = tr.queue_wait_s * 1e3
        self._queue_wait_ms.append(qw_ms)
        self._pct_appends += 1
        self.gauges.on_admit(warm=bool(attrs.get("warm_tokens")))
        self._emit("serving", "request_admitted",
                   value=(None if prefill_s is None
                          else round(prefill_s * 1e3, 3)), tick=tick,
                   rid=tr.rid, queue_wait_ms=round(qw_ms, 3), **attrs)
        if prefill_s is not None:
            self.on_first_token(request, tick, admit_t + prefill_s)

    def on_first_token(self, request, tick: int, t: float) -> None:
        """The request's first generated token exists at engine-clock
        instant ``t`` (the end of its last prefill chunk, or of the
        synchronous prefill).  Emits ``request_first_token`` and
        records the TTFT sample."""
        tr = self._open.get(str(request.rid))
        if tr is None or tr.admit_t is None \
                or tr.first_token_t is not None:
            return
        tr.first_token_t = t
        qw_ms = tr.queue_wait_s * 1e3
        ttft_ms = tr.ttft_s * 1e3
        prefill_ms = tr.prefill_s * 1e3
        self._ttft_ms.append(ttft_ms)
        self._pct_appends += 1
        if self.slo is not None:
            self.slo.record_ttft(self.priority_class(request),
                                 ttft_ms, tick)
        self._emit("serving", "request_first_token",
                   value=round(ttft_ms, 3), tick=tick, rid=tr.rid,
                   ttft_ms=round(ttft_ms, 3),
                   queue_wait_ms=round(qw_ms, 3),
                   prefill_ms=round(prefill_ms, 3))

    def reopen(self, rid: str) -> Optional[RequestTrace]:
        """Reset an open chain's admission/first-token stamps for a
        journal-replayed incarnation (crash recovery): queue wait runs
        from the ORIGINAL submit through the crash downtime to the
        fresh admission, prefill/decode measure the incarnation that
        actually finishes — so the terminal parts still sum to the
        rid's full wall.  Returns the trace, or None when no chain is
        open (a fresh-process replay re-submits normally)."""
        tr = self._open.get(str(rid))
        if tr is None:
            return None
        tr.admit_t = None
        tr.admit_tick = None
        tr.first_token_t = None
        return tr

    def on_done(self, request, tick: int) -> None:
        """Terminal — every submitted rid ends in exactly one of
        these, whatever the reason: ``request.terminal`` names it
        (finished / preempted / deadline / deadline_exceeded / shed;
        absent falls back to the ``request.preempted`` flag)."""
        tr = self._open.pop(str(request.rid), None)
        if tr is None:
            tr = RequestTrace(rid=str(request.rid),
                              prompt_len=len(request.prompt),
                              submit_t=self._clock(), submit_tick=tick)
        t = self._clock()
        tr.done_t = t
        tr.done_tick = tick
        tr.done_wall = self._wall_at(t)
        tr.new_tokens = len(request.out_tokens)
        tr.terminal = getattr(request, "terminal", None) \
            or ("preempted" if request.preempted else "finished")
        tr.preempted = bool(request.preempted)
        # the first latency sample is the prefill; the rest are decode
        # ticks — the per-request inter-token latencies
        cls_name = self.priority_class(request)
        for itl in getattr(request, "token_latency_s", [])[1:]:
            itl_ms = itl * 1e3
            self._itl_ms.append(itl_ms)
            self._pct_appends += 1
            if self.slo is not None:
                self.slo.record_itl(cls_name, itl_ms, tick)
        tps = tr.decode_tokens_per_sec
        if tps is not None:
            self._decode_tps.append(tps)
        self.completed.append(tr)
        self.gauges.on_finish(tr.terminal)
        self.terminals[tr.terminal] = \
            self.terminals.get(tr.terminal, 0) + 1
        if self.slo is not None:
            self.slo.record_terminal(cls_name, tr.terminal, tick)
        attrs: Dict[str, Any] = {
            "rid": tr.rid, "new_tokens": tr.new_tokens,
            "preempted": tr.preempted,
            "terminal": tr.terminal,
            "wall_ms": round(tr.wall_s * 1e3, 3),
            "queue_wait_ms": round(tr.queue_wait_s * 1e3, 3),
            "prefill_ms": round(tr.prefill_s * 1e3, 3),
            "decode_ms": round(tr.decode_s * 1e3, 3),
            "submit_tick": tr.submit_tick,
        }
        if tr.admitted:
            attrs["admit_tick"] = tr.admit_tick
            if tr.ttft_s is not None:
                attrs["ttft_ms"] = round(tr.ttft_s * 1e3, 3)
        if tps is not None:
            attrs["decode_tokens_per_sec"] = round(tps, 2)
        self._emit("serving", "request_done", tick=tick, **attrs)

    # -- engine gauges -------------------------------------------------------

    def on_tick(self, tick: int, **levels) -> None:
        """Called once per engine tick with the level gauges (batch,
        buckets, pool, queue, cumulative compile count); emits on the
        registered cadence."""
        attrs = self.gauges.observe(tick, **levels)
        if attrs is not None:
            self._emit("serve_tick", "serve_tick",
                       value=attrs.get("batch"), tick=tick, **attrs)

    def flush_gauges(self, tick: int) -> None:
        """Emit a trailing partial gauge window (run teardown)."""
        attrs = self.gauges.flush()
        if attrs is not None:
            self._emit("serve_tick", "serve_tick",
                       value=attrs.get("batch"), tick=tick, **attrs)

    # -- derived distributions ----------------------------------------------

    def percentiles(self) -> Dict[str, Optional[float]]:
        """The ServeSummary fields: p50/p99 over the bounded sample
        windows (None until a series has samples)."""
        out: Dict[str, Optional[float]] = {}
        for name, xs in (("queue_wait", self._queue_wait_ms),
                         ("ttft", self._ttft_ms),
                         ("itl", self._itl_ms)):
            for q in (50, 99):
                v = _percentile(xs, q)
                out[f"{name}_p{q}_ms"] = (None if v is None
                                          else round(v, 3))
        return out

    def percentiles_cached(self) -> Dict[str, Optional[float]]:
        """:meth:`percentiles`, recomputed only when a series grew —
        the per-tick exporter publish calls this so idle decode ticks
        never re-sort the sample windows (latency quantiles cost
        amortizes per completed request, not per tick)."""
        if self._pct_cache is None \
                or self._pct_appends != self._pct_mark:
            self._pct_cache = self.percentiles()
            self._pct_mark = self._pct_appends
        return self._pct_cache

    def distributions(self) -> Dict[str, Dict[str, float]]:
        """Full p50/p90/p99 digest for every series (richer than the
        summary fields)."""
        out: Dict[str, Dict[str, float]] = {}
        for name, xs in (("queue_wait_ms", self._queue_wait_ms),
                         ("ttft_ms", self._ttft_ms),
                         ("itl_ms", self._itl_ms),
                         ("decode_tokens_per_sec", self._decode_tps)):
            if not xs:
                continue
            out[name] = {
                "p50": round(_percentile(xs, 50), 3),
                "p90": round(_percentile(xs, 90), 3),
                "p99": round(_percentile(xs, 99), 3),
                "n": len(xs),
            }
        return out

    # -- Chrome export -------------------------------------------------------

    def lane_rows(self) -> List[Dict[str, Any]]:
        return [tr.lane_row() for tr in self.completed]

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON: one lane per completed request
        with queued/prefill/decode phases — loads in Perfetto next to
        a device trace (write with :func:`apex_tpu.monitor.tracing.
        write_chrome_trace`)."""
        return serve_chrome_trace(self.lane_rows())


class SnapshotTrigger:
    """On-demand live-engine-state dump, exactly once per trigger.

    Two sources, mirroring :class:`~apex_tpu.monitor.tracing.
    CaptureTrigger`: a trigger file
    (``APEX_TPU_SERVE_SNAPSHOT_FILE``) existing at a tick boundary
    (consumed), or a signal (SIGUSR1 in the ``--serve`` driver) whose
    handler only sets a flag.  The consuming :meth:`poll` emits ONE
    ``engine_snapshot`` event whose attrs are the engine's
    ``snapshot_state()`` dict — queue depth, active requests and
    their progress, pool/reservation state, compile bookkeeping — the
    post-mortem for a wedged serve (docs/api/serving.md)."""

    def __init__(self, *, trigger_file: Optional[str] = None,
                 signum: Optional[int] = None):
        self.trigger_file = trigger_file
        self.snapshots = 0
        self._pending: Optional[str] = None
        self._signum = signum
        self._prev_handler = None
        if signum is not None:
            import signal as _signal

            try:
                self._prev_handler = _signal.signal(
                    signum, lambda *_: self.request("signal"))
            except ValueError as e:
                # signal.signal only works on the main thread — a
                # trigger built elsewhere keeps its file source
                logger.warning("snapshot signal trigger unavailable: "
                               "%s", str(e)[:120])
                self._signum = None

    @classmethod
    def from_flags(cls, signum: Optional[int] = None
                   ) -> "SnapshotTrigger":
        return cls(trigger_file=flag_str("APEX_TPU_SERVE_SNAPSHOT_FILE"),
                   signum=signum)

    def request(self, reason: str) -> None:
        """Arm a snapshot; consumed at the next :meth:`poll`."""
        if self._pending is None:
            self._pending = reason

    def poll(self, tick: int, state_fn: Callable[[], Dict[str, Any]],
             monitor=None) -> bool:
        """Call once per tick boundary: consume a pending trigger and
        emit the snapshot event.  Returns True iff a snapshot was
        taken by *this* call."""
        if (self.trigger_file is not None and self._pending is None
                and os.path.exists(self.trigger_file)):
            try:
                os.unlink(self.trigger_file)
            except OSError as e:
                # the file cannot be consumed, so it would re-arm on
                # every tick — take this one snapshot, then retire
                # the file source (exactly-once must survive a
                # read-only trigger directory)
                logger.warning("snapshot trigger file unlink failed "
                               "(disabling the file trigger): %s",
                               str(e)[:120])
                self.trigger_file = None
            self._pending = "file"
        if self._pending is None:
            return False
        reason, self._pending = self._pending, None
        try:
            state = dict(state_fn())
        except Exception as e:  # telemetry must never kill the serve
            logger.warning("engine snapshot state failed: %s",
                           str(e)[:160])
            state = {"error": str(e)[:200]}
        self.snapshots += 1
        if monitor is not None:
            monitor.event("serving", "engine_snapshot", step=tick,
                          reason=reason, **state)
        return True

    def close(self) -> None:
        """Restore the signal handler."""
        if self._signum is not None and self._prev_handler is not None:
            import signal as _signal

            _signal.signal(self._signum, self._prev_handler)
            self._prev_handler = None
