"""Deterministic-interleaving schedule harness — the dynamic half of
the APX8xx host-concurrency audit.

The static auditor (:mod:`.concurrency`) proves the lock discipline
*as written*; this module stresses the discipline *as executed*: a
seeded cooperative scheduler serializes the threaded serving fleet's
replica threads at their tick boundaries in a *permuted, reproducible*
order, so the same request trace runs under many different
interleavings — and the terminal fleet digest (every request's output
tokens) must be **seed-invariant**.  A cross-thread race that feeds
back into outputs, a lost update in shared bookkeeping, or a
background thread dying silently shows up as a digest mismatch, a
lost request, or a captured ``threading.excepthook`` failure instead
of a once-a-month production mystery.

Three pieces:

* :class:`DeterministicScheduler` — a condition-variable gate every
  replica thread passes at each tick boundary
  (:meth:`~apex_tpu.serving.fleet.FleetRouter.serve_threaded`'s
  ``scheduler`` hook).  Exactly one thread runs between gates; the
  next runner is drawn from a ``random.Random(seed)`` stream, so one
  seed is one total order and five seeds are five genuinely different
  interleavings — each reproducible bit-for-bit.
* :func:`run_fleet_seed` / :func:`schedule_sweep` — build the smoke-
  GPT fleet (same construction as ``standalone_gpt --serve-fleet``),
  serve one fixed request trace per seed under the gate, and report
  per-seed digests plus any :class:`~apex_tpu.monitor.events.
  ThreadExceptionCapture` failures.
* the CLI — ``python -m apex_tpu.analysis.schedule`` (ci.sh step 13):
  N seeds (``APEX_TPU_SCHED_SEEDS``) x the 2-replica threaded fleet,
  asserting identical digests, zero lost requests, and zero uncaught
  thread exceptions.

Everything here is host-side and CPU-friendly; the scheduler is a
test/CI instrument, never a production code path.
"""
from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Set

from .flags import flag_int

__all__ = ["DeterministicScheduler", "ScheduleTimeout", "SeedRun",
           "SweepReport", "fleet_digest", "process_sweep",
           "run_fleet_seed", "run_process_fleet_seed",
           "schedule_sweep", "main"]


class ScheduleTimeout(RuntimeError):
    """A gated thread waited past the scheduler timeout — some other
    thread wedged while holding the schedule slot."""


class DeterministicScheduler:
    """Seeded cooperative serializer for thread tick boundaries.

    Threads are announced up-front with :meth:`expect` (main thread,
    before they start), call :meth:`gate` at every tick boundary, and
    :meth:`finish` on exit (``finally``).  At any instant at most one
    expected thread is *granted*; when the grant holder reaches its
    next gate (or finishes), the next holder is drawn from the seeded
    stream over the still-active threads.  The grant sequence
    (:attr:`grants`) is a pure function of the seed and the threads'
    lifetimes — the reproducible interleaving.

    The gate itself is the canonical condition-variable wait (the
    ``Condition.wait``-releases-the-lock idiom APX804 exempts); a
    thread that waits past ``timeout`` raises :class:`ScheduleTimeout`
    rather than hanging CI.
    """

    def __init__(self, seed: int, *, timeout: float = 120.0):
        self.seed = int(seed)
        self.timeout = float(timeout)
        self._rng = random.Random(int(seed))
        self._cv = threading.Condition()
        self._active: Set[str] = set()
        self._current: Optional[str] = None
        # a grant is *pending* until its thread passes the gate
        # (claimed); the holder's NEXT gate call releases it.  A
        # thread arriving at a grant it has not consumed yet takes it
        # — it must not re-roll someone else's turn away.
        self._claimed = False
        self.grants: List[str] = []

    def expect(self, name: str) -> None:
        """Announce a thread (call before it starts)."""
        with self._cv:
            self._active.add(str(name))

    def gate(self, name: str) -> None:
        """Tick boundary: release a held grant, then block until the
        seeded stream hands a fresh one back."""
        name = str(name)
        deadline = time.monotonic() + self.timeout
        with self._cv:
            if name not in self._active:
                return
            if self._current == name and self._claimed:
                self._current = None
                self._pick_locked()
            elif self._current is None:
                self._pick_locked()
            while not (self._current == name and not self._claimed):
                if name not in self._active:
                    return
                remaining = deadline - time.monotonic()
                if remaining <= 0.0:
                    raise ScheduleTimeout(
                        f"thread {name!r} starved at the schedule "
                        f"gate for {self.timeout:.0f}s (current "
                        f"grant: {self._current!r})")
                self._cv.wait(min(remaining, 1.0))
            self._claimed = True

    def finish(self, name: str) -> None:
        """Thread exit: leave the pool and hand the grant on."""
        with self._cv:
            name = str(name)
            self._active.discard(name)
            if self._current == name:
                self._current = None
                self._pick_locked()
            elif self._current is None and self._active:
                self._pick_locked()
            self._cv.notify_all()

    def _pick_locked(self) -> None:
        if self._current is None and self._active:
            self._current = self._rng.choice(sorted(self._active))
            self._claimed = False
            self.grants.append(self._current)
        self._cv.notify_all()


# ---------------------------------------------------------------------------
# The fleet stress sweep
# ---------------------------------------------------------------------------

def fleet_digest(router) -> str:
    """Deterministic digest of a whole fleet's terminal output: each
    replica's :meth:`~apex_tpu.serving.engine.ServingEngine.
    tokens_digest` folded in replica order.  Identical digests across
    scheduler seeds == token-for-token identical fleet output under
    every tried interleaving."""
    import hashlib

    h = hashlib.md5()
    for r in sorted(router.replicas, key=lambda x: str(x.replica_id)):
        h.update(f"{r.replica_id}="
                 f"{r.engine.tokens_digest()};".encode())
    return h.hexdigest()[:12]


@dataclasses.dataclass
class SeedRun:
    """One seed's outcome."""

    seed: int
    digest: str
    tokens: int
    requests_done: int
    lost: int
    grants: int                 # schedule hand-offs taken
    thread_failures: List[Dict[str, Any]]


@dataclasses.dataclass
class SweepReport:
    """What :func:`schedule_sweep` measured across every seed."""

    runs: List[SeedRun]

    @property
    def digests(self) -> Dict[int, str]:
        return {r.seed: r.digest for r in self.runs}

    @property
    def invariant(self) -> bool:
        return len({r.digest for r in self.runs}) <= 1

    def failures(self) -> List[str]:
        out = []
        if not self.invariant:
            out.append(f"terminal digest is NOT seed-invariant: "
                       f"{self.digests} — a thread interleaving "
                       f"changed the fleet's output")
        for r in self.runs:
            if r.lost:
                out.append(f"seed {r.seed}: {r.lost} lost request(s)")
            for f in r.thread_failures:
                out.append(f"seed {r.seed}: background thread "
                           f"{f.get('thread')!r} died: "
                           f"{f.get('error')}: {f.get('message')}")
        return out


def run_fleet_seed(seed: int, *, replicas: int = 2,
                   num_requests: int = 6, new_tokens: int = 4,
                   hidden: int = 32, num_layers: int = 2,
                   timeout: float = 120.0, **fleet_kw) -> SeedRun:
    """Serve one fixed request trace (request RNG pinned to 0) on a
    fresh threaded fleet under the seeded schedule gate.  Background-
    thread exceptions are captured (not just printed) and returned on
    the :class:`SeedRun`."""
    from ..monitor.events import (BackgroundThreadError,
                                  ThreadExceptionCapture)
    from ..serving import BucketLadder
    from ..testing.standalone_gpt import fleet_smoke

    sched = DeterministicScheduler(seed, timeout=timeout)
    cap = ThreadExceptionCapture().install()
    summary = router = None
    try:
        summary, router = fleet_smoke(
            num_requests, replicas=replicas, threads=True,
            scheduler=sched, max_new_tokens=new_tokens,
            hidden=hidden, num_layers=num_layers,
            ladder=BucketLadder(batch=(2, 4), pages=(2, 4)),
            num_blocks=32, block_size=4, seed=0,
            return_router=True, **fleet_kw)
    except BackgroundThreadError:
        # already captured in cap.failures; the SeedRun reports it
        pass
    finally:
        cap.uninstall()
    failures = [{k: v for k, v in f.items() if k != "exception"}
                for f in cap.failures]
    return SeedRun(
        seed=int(seed),
        digest=fleet_digest(router) if router is not None else "",
        tokens=summary.tokens_generated if summary else 0,
        requests_done=summary.requests_done if summary else 0,
        lost=summary.lost_requests if summary else num_requests,
        grants=len(sched.grants),
        thread_failures=failures)


def schedule_sweep(seeds: Sequence[int], **kw) -> SweepReport:
    """Run :func:`run_fleet_seed` for every seed; the report's
    :meth:`~SweepReport.failures` is empty iff the fleet's terminal
    digest is identical across all of them with zero lost requests
    and zero uncaught thread exceptions."""
    return SweepReport(runs=[run_fleet_seed(s, **kw) for s in seeds])


def run_process_fleet_seed(seed: int, *, replicas: int = 2,
                           num_requests: int = 4,
                           new_tokens: int = 3, hidden: int = 16,
                           num_layers: int = 1,
                           **fleet_kw) -> SeedRun:
    """The ISSUE-18 process-boundary twin of :func:`run_fleet_seed`:
    one fixed request trace (request RNG pinned to 0) served by the
    PROCESS-isolated fleet, with ``seed`` permuting the supervisor's
    per-round replica tick order instead of a thread schedule.  The
    fleet digest (journal-merged, routing-invariant) must not care —
    crash-reshuffled or seed-reshuffled, greedy decode is
    interleaving-invariant across process boundaries too.  ``grants``
    reports supervisor rounds (the closest analogue of schedule
    hand-offs)."""
    from ..testing.standalone_gpt import fleet_procs_smoke

    summary = fleet_procs_smoke(
        num_requests, replicas=replicas, max_new_tokens=new_tokens,
        hidden=hidden, num_layers=num_layers, num_heads=2,
        decode_attention="reference", seed=0, tick_seed=int(seed),
        **fleet_kw)
    return SeedRun(
        seed=int(seed), digest=summary.digest,
        tokens=summary.tokens_generated,
        requests_done=summary.requests_done,
        lost=summary.lost_requests, grants=summary.rounds,
        thread_failures=[])


def process_sweep(seeds: Sequence[int], **kw) -> SweepReport:
    """:func:`schedule_sweep` across the process boundary: every seed
    drives :func:`run_process_fleet_seed`; same :class:`SweepReport`
    invariant (identical digest, zero lost) over subprocess fleets."""
    return SweepReport(runs=[run_process_fleet_seed(s, **kw)
                             for s in seeds])


# ---------------------------------------------------------------------------
# CLI — ci.sh step 13's stress leg
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.analysis.schedule",
        description="Seeded deterministic-schedule fleet stress: N "
                    "seeds x the threaded serving fleet under "
                    "permuted tick interleavings; fails unless every "
                    "seed produces the identical terminal digest "
                    "with zero lost requests and zero uncaught "
                    "background-thread exceptions.")
    ap.add_argument("--seeds", type=int, default=None,
                    help="number of seeds to sweep (default: "
                         "APEX_TPU_SCHED_SEEDS)")
    ap.add_argument("--base-seed", type=int, default=0,
                    help="first seed (default 0)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-gate starvation timeout (seconds)")
    ap.add_argument("--procs", action="store_true",
                    help="sweep the PROCESS-isolated fleet instead "
                         "(ISSUE-18): each seed permutes the "
                         "supervisor's per-round replica tick order "
                         "across subprocess boundaries; the journal-"
                         "merged fleet digest must be identical")
    args = ap.parse_args(argv)

    n = args.seeds if args.seeds is not None \
        else flag_int("APEX_TPU_SCHED_SEEDS")
    if n < 1:
        ap.error(f"--seeds must be >= 1, got {n} (a zero-seed sweep "
                 f"proves nothing)")
    if args.procs:
        report = process_sweep(
            range(args.base_seed, args.base_seed + n),
            replicas=args.replicas, num_requests=args.requests,
            new_tokens=args.new_tokens)
    else:
        report = schedule_sweep(
            range(args.base_seed, args.base_seed + n),
            replicas=args.replicas, num_requests=args.requests,
            new_tokens=args.new_tokens, timeout=args.timeout)
    for r in report.runs:
        print(f"[schedule] seed {r.seed}: digest={r.digest} "
              f"done={r.requests_done} tokens={r.tokens} "
              f"lost={r.lost} grants={r.grants} "
              f"thread_failures={len(r.thread_failures)}")
    failures = report.failures()
    for f in failures:
        print(f"[schedule] FAIL: {f}", file=sys.stderr)
    if failures:
        return 1
    print(f"[schedule] OK: {n} seed(s), identical terminal digest "
          f"{report.runs[0].digest} across every interleaving, "
          f"0 lost requests, 0 uncaught thread exceptions")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
