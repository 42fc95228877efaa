"""apex_tpu.resilience — turn failures into resumed runs.

PR 2's :mod:`apex_tpu.monitor` built the eyes (structured telemetry,
watchdog alarms); this package is the hands.  Four pieces, spanning the
checkpoint, monitor, and driver layers:

1. **AutoResume** (:mod:`.autoresume`) — the realized ADLR autoresume
   hook: SIGTERM/SIGINT set a flag the loop polls at step boundaries
   (``termination_requested()``, wired into the Megatron-parity
   ``get_autoresume()``), enabling a final synchronous checkpoint and a
   ``CLEAN_EXIT.json`` marker instead of a corpse.

2. **Checkpoint integrity** — lives in
   :mod:`apex_tpu.utils.checkpoint`: ``latest_valid_step()`` spots
   partial/unfinalized step dirs structurally; ``restore()`` falls back
   step-by-step past corrupt ones (emitting ``ckpt_skipped`` /
   ``ckpt_gc`` events and GC'ing the garbage) and names the available
   steps when an explicitly requested step is missing.

3. **Retrying driver** (:mod:`.driver`) — :func:`run_resumable`:
   bounded restarts, exponential backoff + per-process jitter, every
   attempt / give-up on the event log; paired with
   :class:`~.escalation.EscalationPolicy`, which turns watchdog alarms
   into checkpoint-then-abort restarts via :class:`EscalationAbort`.

4. **Fault injection** (:mod:`.faults`) — deterministic injectors
   (``crash@K`` / ``kill@K`` / ``sigterm@K`` / ``nan@K`` / ``stall@K``
   and on-disk checkpoint corruption) proving kill-at-K + resume
   reproduces the uninterrupted run bitwise (tests/test_resilience.py,
   ``--fault`` on the smoke drivers, tools/ci.sh step 4).

Full lifecycle walkthrough + escalation table: docs/api/resilience.md.
"""
from .autoresume import CLEAN_EXIT_MARKER, AutoResume, read_clean_exit
from .driver import GiveUp, backoff_delay, run_resumable
from .escalation import (
    ABORT,
    CHECKPOINT_THEN_ABORT,
    DEFAULT_POLICY,
    DEFAULT_SERVE_POLICY,
    IGNORE,
    SNAPSHOT_THEN_DRAIN,
    EscalationAbort,
    EscalationPolicy,
    serve_policy,
)
from .faults import (
    PARENT_KINDS,
    PROCESS_FATAL_KINDS,
    FaultInjector,
    InjectedCrash,
    InjectedFault,
    corrupt_checkpoint,
    corrupt_journal,
    parse_fault,
    split_fault,
)

__all__ = [
    "AutoResume", "read_clean_exit", "CLEAN_EXIT_MARKER",
    "run_resumable", "backoff_delay", "GiveUp",
    "EscalationPolicy", "EscalationAbort", "DEFAULT_POLICY",
    "DEFAULT_SERVE_POLICY", "serve_policy",
    "IGNORE", "ABORT", "CHECKPOINT_THEN_ABORT", "SNAPSHOT_THEN_DRAIN",
    "FaultInjector", "parse_fault", "split_fault",
    "PARENT_KINDS", "PROCESS_FATAL_KINDS",
    "InjectedFault", "InjectedCrash",
    "corrupt_checkpoint", "corrupt_journal",
]
