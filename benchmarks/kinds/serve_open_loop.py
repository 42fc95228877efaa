"""``kind: serve_open_loop`` -- requests arrive on a schedule drawn
from ``--seed`` (``traffic.py``) whether or not earlier ones have
finished; the benchmark drives ``ServingEngine.submit()`` / ``.step()``
itself and times everything on its own clock.

A token is *delivered* when the ``step()`` that produced it returns:
the engine has no streaming hook, so that is when a caller first holds
it.  A request's first delivery carries the prefill's token and the
same tick's decode token.

* time to first token: due instant -> first delivery;
* gap between tokens: between successive deliveries of one request
  (lead-in requests included), counted if it ends inside the window;
* tokens per second: tokens delivered from the opening of the window to
  the first ``step()`` return at or after ``--seconds``, over that
  time.

The window opens on a primed engine: ``lead_in_s`` seconds of the same
arrival process run before it and count as set-up.  With
``follow_to_completion`` the requests due in the window are followed
until each has ended (no new arrivals; tokens and gaps after the close
are not counted); without, the run stops at the close and what is
still queued or running is neither followed nor failed.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import re
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from ..common import CellResult, CompileCounter, check, say
from ..stats import percentile
from ..trace import load_trace, ops_in_runs, ops_matching, runs_matching
from ..traffic import open_loop_schedule

# The system computes in bf16, the reference in float32.  Random
# weights give near-ties all the time, so tokens are not compared: the
# emitted token's reference logit must lie within this margin of the
# reference's largest.  Measured margins are 0 for ~96% of tokens, mean
# 3e-4 and at most 0.015 (PR 23), against a spread of the logits at one
# position of ~0.5: a token taken from a wrong position, a stale cache
# page or an int8 path lands a whole spread away.
LOGIT_MARGIN = 0.05


# the flash-decode kernel's call, as the trace names it: its output is
# bf16[batch rung, packed heads, 1, head_dim] and its first operand the
# block table, s32[batch rung, page rung]
DECODE_CALL = r"= bf16\[(\d+),\d+,1,\d+\]\S* custom-call\("
BLOCK_TABLE = DECODE_CALL + r"s32\[(\d+),(\d+)\]"


def traced_grid(trace) -> dict:
    """What the device ran while the profiler was on, from its trace:
    the decode ticks, the batch dimension of their decode calls and the
    slots of their block tables, summed -- what ``decode_grid``'s
    ``ticks``, ``grid_rows`` and ``grid_pages``, taken from the
    engine's own events, have to equal.  Of the first device that ran a
    tick (under tensor parallelism each device runs every tick);
    ``traced_grid_pages`` is None where a decode call does not show its
    block table."""
    for d in trace.devices:
        runs = runs_matching(d, r"^jit_step\(", DECODE_CALL)
        if not runs:
            continue
        calls = {}                    # the first decode call of each tick
        for i, op in ops_in_runs(runs, ops_matching(d.ops, DECODE_CALL)):
            calls.setdefault(i, op.name)
        tables = [re.search(BLOCK_TABLE, name) for name in calls.values()]
        return dict(
            traced_ticks=len(runs),
            traced_grid_rows=sum(int(re.search(DECODE_CALL, name)[1])
                                 for name in calls.values()),
            traced_grid_pages=sum(int(m[2]) * int(m[3]) for m in tables)
            if all(tables) else None)
    return dict(traced_ticks=0, traced_grid_rows=0, traced_grid_pages=0)


def hold_grid_to_trace(faults, grid, trace) -> None:
    """The fills stand on the engine's events: a run whose events and
    device trace disagree on the ticks, the rows or the slots launched
    is not correct.  (Not called where the trace has no device plane,
    as on the CPU: there is nothing to hold the events to.)"""
    seen = traced_grid(trace)
    say(**seen)
    for key in ("ticks", "grid_rows", "grid_pages"):
        counted = (grid or {}).get(key, 0)
        check(faults, seen["traced_" + key] in (None, counted),
              f"decode_grid counts {key}={counted} where the device "
              f"trace holds {seen['traced_' + key]}")


class TickLog:
    """Stands in for the engine's monitor while the profiler is on and
    keeps the ``decode_step`` events: the rows and the rungs of each
    tick as the engine itself launched it (``batch``, ``batch_bucket``,
    ``pages_bucket``)."""

    def __init__(self):
        self.ticks: List[dict] = []

    def event(self, kind, name, value=None, **attrs):
        if name == "decode_step":
            self.ticks.append(attrs)


@dataclasses.dataclass
class Track:
    request: object
    due: float                    # absolute, on the benchmark's clock
    in_window: bool
    rejected: bool = False
    seen: int = 0
    deliveries: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)     # (instant, tokens held after it)


@dataclasses.dataclass
class Drive:
    """The raw record of one driven span."""

    tracks: List[Track]
    opened: float
    closed: float                 # first step() return >= opened+seconds
    lateness_s: List[float]
    queue_mid: int
    queue_close: int
    active_close: int
    decode_ticks: int
    compiles_in_window: int
    decode_shapes: Optional[dict]     # summed while the profiler ran
    decode_grid: Optional[dict]       # likewise: live work against the
    #                                   rungs each tick was launched on
    engine_steps: int
    due_in_window: int                # scheduled, submitted or not


def drive(job, traffic, *, seed, seconds, trace_dir=None,
          clock=time.perf_counter) -> Drive:
    engine = job.engine
    schedule = open_loop_schedule(traffic, seed, seconds, job.vocab)
    follow = traffic["follow_to_completion"]
    block = job.facts["decode_geometry"]["block_size"]
    start = clock()
    opened = start + float(traffic.get("lead_in_s", 0.0))
    close_at = opened + seconds
    pending = collections.deque(schedule)
    tracks: List[Track] = []
    live: List[Track] = []
    lateness: List[float] = []
    queue_mid = queue_close = active_close = -1
    closed = None
    decode_ticks = engine_steps = 0
    trace_on = traced = False
    shapes = dict(live_pages=0, live_tokens=0, rows=0)
    grid = dict(ticks=0, rows=0, grid_rows=0, live_pages=0, grid_pages=0)
    log, monitor_was = TickLog(), engine.monitor
    compiles_at_open = None
    with CompileCounter() as compiles:
        while True:
            now = clock()
            if compiles_at_open is None and now >= opened:
                compiles_at_open = compiles.count
            if queue_mid < 0 and now >= opened + seconds / 2:
                queue_mid = len(engine.queue)
            # the profiler sees the window's last seconds: writing the
            # trace out stalls the host for seconds (6 s seen on the
            # chip), and there the stall falls after the close
            if trace_dir and not traced and not trace_on \
                    and now >= close_at - min(traffic["trace_seconds"],
                                              seconds / 2):
                jax.profiler.start_trace(trace_dir)
                trace_on, engine.monitor = True, log
            if trace_on and now >= close_at:
                jax.profiler.stop_trace()
                trace_on, traced, engine.monitor = False, True, monitor_was
            while pending and opened + pending[0].due_s <= now:
                a = pending.popleft()
                due = opened + a.due_s
                req = job.make_request(a.rid, a.prompt, a.max_new_tokens)
                req.submit_t = due    # the engine's queue-wait then
                #                       runs from the due instant too
                t = Track(req, due, in_window=a.rid.startswith("req"))
                tracks.append(t)
                try:
                    engine.submit(req)
                    live.append(t)
                except ValueError:
                    t.rejected = True
                if t.in_window:
                    lateness.append(now - due)
            if engine.queue or engine.active or engine.prefilling:
                with jax.profiler.TraceAnnotation("bench.engine_step"):
                    n = engine.step()
                now = clock()
                engine_steps += 1
                for t in live:
                    held = len(t.request.out_tokens)
                    if held > t.seen:
                        t.deliveries.append((now, held))
                        t.seen = held
                live = [t for t in live if t.request.terminal is None]
                if n > 0:
                    decode_ticks += 1
                    if trace_on:
                        pages = 0
                        for q in engine.active.values():
                            kv = len(q.prompt) + len(q.out_tokens) - 1
                            shapes["live_tokens"] += kv
                            pages += -(-kv // block)
                        shapes["live_pages"] += pages
                        shapes["rows"] += n
                        # the live pages are the benchmark's count (the
                        # tick's requests are still the active ones: the
                        # finished leave at the next step's start); the
                        # rows and the rungs are the engine's own word
                        grid["live_pages"] += pages
                        for tick in log.ticks:
                            grid["ticks"] += 1
                            grid["rows"] += tick["batch"]
                            grid["grid_rows"] += tick["batch_bucket"]
                            grid["grid_pages"] += tick["batch_bucket"] \
                                * tick["pages_bucket"]
                        log.ticks.clear()
            elif pending:
                time.sleep(max(0.0, min(
                    opened + pending[0].due_s - now, 0.002)))
            if closed is None and now >= close_at:
                closed = now
                queue_close = len(engine.queue)
                active_close = len(engine.active)
            if closed is not None and not trace_on and (
                    not follow or not any(t.in_window for t in live)):
                break
        compiles_in_window = compiles.count - (compiles_at_open or 0)
    return Drive(tracks, opened, closed, lateness, queue_mid, queue_close,
                 active_close, decode_ticks, compiles_in_window,
                 dict(shapes, **job.facts["decode_geometry"])
                 if traced and shapes["rows"] else None,
                 grid if traced and grid["ticks"] else None, engine_steps,
                 sum(a.rid.startswith("req") for a in schedule))


def measures(d: Drive) -> dict:
    """The latency and rate arithmetic over a driven span."""
    ttft, gaps, tokens = [], [], 0
    for t in d.tracks:
        if t.in_window and t.deliveries:
            ttft.append(1e3 * (t.deliveries[0][0] - t.due))
        prev_t, prev_n = None, 0
        for at, held in t.deliveries:
            if prev_t is not None and d.opened < at <= d.closed:
                gaps.append(1e3 * (at - prev_t))
            if d.opened < at <= d.closed:
                tokens += held - prev_n
            prev_t, prev_n = at, held
    return dict(
        ttft_ms=ttft, itl_ms=gaps, tokens=tokens,
        ttft_p50_ms=percentile(ttft, 50), ttft_p90_ms=percentile(ttft, 90),
        itl_p50_ms=percentile(gaps, 50), itl_p95_ms=percentile(gaps, 95),
        serve_tokens_per_s=tokens / (d.closed - d.opened))


def reference_check(job, traffic, tracks, seed, faults) -> dict:
    """A seeded sample of finished window requests, prompt + output in
    one full reference forward each."""
    done = [t for t in tracks if t.in_window
            and t.request.terminal == "finished"]
    rng = np.random.default_rng([int(seed), 0x5A3B1E])
    width = traffic["max_total_tokens"]
    picks = rng.choice(len(done), min(traffic["reference_sample"],
                                      len(done)), replace=False) \
        if done else []
    worst = total = count = exact = 0
    spread = None
    for i in picks:
        r = done[int(i)].request
        seq = list(r.prompt) + list(r.out_tokens)
        tokens = np.zeros((1, width), np.int32)
        emitted = np.zeros((1, width), np.int32)
        tokens[0, :len(seq)] = seq
        emitted[0, :len(seq) - 1] = seq[1:]
        margins, spreads = job.reference_margins(tokens, emitted)
        lo, hi = len(r.prompt) - 1, len(seq) - 1
        m = np.asarray(margins)[0, lo:hi]
        spread = float(np.asarray(spreads)[0, lo:hi].mean())
        worst = max(worst, float(m.max()))
        total += float(m.sum())
        count += m.size
        exact += int((m == 0).sum())
    check(faults, count > 0, "no finished request to hold against the "
                             "reference")
    check(faults, worst <= LOGIT_MARGIN,
          f"an emitted token lies {worst} under the reference's largest "
          f"logit (allowed {LOGIT_MARGIN})")
    return dict(reference_checked=f"{len(picks)}req/{count}tok",
                reference_argmax=exact,
                reference_max_margin=round(worst, 5),
                reference_mean_margin=round(total / max(count, 1), 6),
                reference_logit_spread=spread)


def run(job, traffic, *, seed, seconds, trace_dir, platform,
        peaks) -> CellResult:
    faults = []
    engine = job.engine
    t0 = time.perf_counter()
    programs = engine.warmup()
    warmup_s = time.perf_counter() - t0
    decode_fn = getattr(engine, "_decode_fn", None)
    kernels = "unknown"
    if decode_fn is not None:
        ladder = engine.ladder
        kernels = decode_fn(ladder.max_batch, ladder.max_pages) \
            .as_text().count("tpu_custom_call")
        check(faults, kernels > 0 or platform != "tpu",
              "the decode step holds no Mosaic kernel: a silent twin")

    d = drive(job, traffic, seed=seed, seconds=seconds,
              trace_dir=trace_dir)
    m = measures(d)
    window = [t for t in d.tracks if t.in_window]
    follow = traffic["follow_to_completion"]
    ok_ends = ("finished",) if follow else ("finished", None)
    failed = sum(1 for t in window
                 if t.rejected or t.request.terminal not in ok_ends)
    finished = sum(1 for t in window
                   if t.request.terminal == "finished")
    check(faults, failed == 0,
          f"{failed} of {len(window)} due requests did not end finished")
    check(faults, d.compiles_in_window == 0,
          f"{d.compiles_in_window} programs lowered inside the window")
    check(faults, bool(m["itl_ms"]) and bool(m["ttft_ms"]),
          "no token was delivered inside the window")
    ref = reference_check(job, traffic, d.tracks, seed, faults)

    waits = {tr.rid: 1e3 * tr.queue_wait_s
             for tr in getattr(engine.metrics, "completed", ())}
    waits = [waits[t.request.rid] for t in window
             if t.request.rid in waits]
    # every number of the [bench] lines is a fact too, so a later
    # per-layer metric can read one through ``engine_fact`` by name
    facts = dict(job.facts, decode_shapes=d.decode_shapes,
                 decode_grid=d.decode_grid,
                 queue_wait_p90_ms=percentile(waits, 90),
                 **{k: v for k, v in m.items() if k.endswith(("_ms", "_s"))
                    and not isinstance(v, list)})
    late = [1e3 * x for x in d.lateness_s]
    # a run that stops at the close may not have reached its last
    # arrivals: they were due all the same
    say(requests_due=d.due_in_window, failed=failed, finished=finished,
        lead_in=len(d.tracks) - len(window), programs=len(programs),
        warmup_s=round(warmup_s, 1), kernels_in_decode=kernels,
        compiles_in_window=d.compiles_in_window,
        engine_steps=d.engine_steps, decode_ticks=d.decode_ticks,
        itl_gaps=len(m["itl_ms"]), **ref)
    say(offered_rps=traffic["rate_per_s"],
        window_s=round(d.closed - d.opened, 4),
        ttft_p50_ms=_r(m["ttft_p50_ms"]), ttft_p90_ms=_r(m["ttft_p90_ms"]),
        itl_p50_ms=_r(m["itl_p50_ms"]), itl_p95_ms=_r(m["itl_p95_ms"]),
        serve_tokens_per_s=_r(m["serve_tokens_per_s"]),
        queue_mid=d.queue_mid, queue_close=d.queue_close,
        active_close=d.active_close,
        queue_wait_p90_ms=_r(facts["queue_wait_p90_ms"]),
        lateness_p50_ms=_r(percentile(late, 50)),
        lateness_max_ms=_r(max(late, default=None)))
    trace = load_trace(trace_dir) if trace_dir else None
    if d.decode_grid:
        say(decode_grid=json.dumps(d.decode_grid))
    if trace is not None and any(dev.modules for dev in trace.devices):
        hold_grid_to_trace(faults, d.decode_grid, trace)
    return CellResult(
        correct=not faults, attempted=d.due_in_window, failed=failed,
        end_to_end={k: m[k] for k in ("ttft_p90_ms", "itl_p95_ms",
                                      "serve_tokens_per_s")
                    if m[k] is not None},
        window_opened_at=d.opened, facts=facts, trace=trace,
        faults=faults)


def _r(x, digits=3):
    return None if x is None else round(x, digits)
