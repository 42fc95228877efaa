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
import time
from typing import List, Optional, Tuple

import jax
import numpy as np

from ..common import CellResult, CompileCounter, check, say
from ..stats import percentile
from ..trace import load_trace
from ..traffic import open_loop_schedule

# The system computes in bf16, the reference in float32.  Random
# weights give near-ties all the time, so tokens are not compared: the
# emitted token's reference logit must lie within this margin of the
# reference's largest.  Measured margins are 0 for ~96% of tokens, mean
# 3e-4 and at most 0.015 (PR 23), against a spread of the logits at one
# position of ~0.5: a token taken from a wrong position, a stale cache
# page or an int8 path lands a whole spread away.
LOGIT_MARGIN = 0.05


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
                trace_on = True
            if trace_on and now >= close_at:
                jax.profiler.stop_trace()
                trace_on, traced = False, True
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
                        for q in engine.active.values():
                            kv = len(q.prompt) + len(q.out_tokens) - 1
                            shapes["live_tokens"] += kv
                            shapes["live_pages"] += -(-kv // block)
                        shapes["rows"] += n
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
                 if traced and shapes["rows"] else None, engine_steps,
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
    return CellResult(
        correct=not faults, attempted=d.due_in_window, failed=failed,
        end_to_end={k: m[k] for k in ("ttft_p90_ms", "itl_p95_ms",
                                      "serve_tokens_per_s")
                    if m[k] is not None},
        window_opened_at=d.opened, facts=facts,
        trace=load_trace(trace_dir) if trace_dir else None,
        faults=faults)


def _r(x, digits=3):
    return None if x is None else round(x, digits)
