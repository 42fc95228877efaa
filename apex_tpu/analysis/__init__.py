"""apex_tpu.analysis — static and runtime correctness tooling.

The TPU-native counterpart of the reference repo's build/test matrix
(ref: tests/docker_extension_builds): instead of linting CUDA builds,
lint the *tracing* discipline the whole framework depends on.

Five pieces (rules registered centrally in :mod:`.rules`, docs table
generated from it):

* :mod:`.flags` — the central registry of every ``APEX_TPU_*``
  environment flag (name, type, default, doc) with typed accessors.
  Library code reads flags ONLY through it; the linter enforces that.
* :mod:`.linter` — AST trace-safety linter: host syncs on traced
  values, Python truthiness on tracers, env reads inside traced code,
  bare/broad excepts, direct ``jax.shard_map`` use (rule table in
  docs/api/analysis.md).
* :mod:`.parity` — kernel-parity audit: every ``pallas_call`` site in
  ``ops/`` must name a registered jnp twin and a test referencing both.
* :mod:`.hlo` — compiled-graph auditor over the lowered jaxprs /
  StableHLO of every registered entry point
  (:mod:`apex_tpu.testing.entry_points`): missed donations, silent
  dtype promotions, the collective census and a peak-live-memory
  estimate diffed against ``tools/hlo_baseline.json``.
* :mod:`.sharding` — SPMD sharding auditor over the *partitioned*
  multichip entries: declared :class:`apex_tpu.mesh_plan.MeshPlan`
  specs vs the partitioner's propagated shardings, reshard chains,
  overlap advisories, and per-device memory diffed against
  ``tools/sharding_baseline.json``.
* :mod:`.sanitizer` — runtime ``sanitize()`` context: JAX transfer
  guard plus a per-step recompile budget driven by ``jax_log_compiles``.
* :mod:`.concurrency` — host-concurrency auditor (APX801-805): lock
  discipline via guard inference over ``with self._lock:`` regions,
  lock-acquisition-order cycles aggregated cross-module, flag-only
  signal handlers, blocking-under-lock, and thread-target jit
  dispatch outside a device pin.
* :mod:`.protocol` — wire-protocol + resource-lifecycle auditor
  (APX901-905): ``serving/`` + ``resilience/`` audited against the
  declared ``ProtocolSpec`` registry in ``serving/control_plane.py``
  — deadline discipline, op and header-field drift matched across
  the parent/child modules, socket/subprocess/tempdir lifecycle,
  and retry-safety.
* :mod:`.schedule` — the dynamic half: a seeded deterministic-
  interleaving scheduler that steps the threaded serving fleet under
  permuted thread orderings and asserts the terminal digest is
  seed-invariant, with ``threading.excepthook`` capture so a
  background-thread crash is a failure, not a vanished thread.

CLI: ``python -m apex_tpu.analysis --check`` / ``--check-hlo`` /
``--check-sharding`` (self-hosted in tools/ci.sh steps 6, 7, and 11;
see ``--help`` for the rest).
"""
# flags is the one submodule production code imports at module scope
# (ops/amp/monitor read the registry on import); keep this package
# __init__ from dragging the linter/parity/sanitizer machinery into
# every library import path — tooling symbols resolve lazily (PEP 562).
from .flags import (FLAGS, Flag, flag_bool, flag_float, flag_int,
                    flag_str, render_flag_table)

_LAZY = {
    "Finding": "linter", "lint_paths": "linter",
    "load_baseline": "linter", "run_check": "linter",
    "audit_kernel_parity": "parity",
    "RecompileBudgetExceeded": "sanitizer", "Sanitizer": "sanitizer",
    "sanitize": "sanitizer", "sanitize_smoke": "sanitizer",
    "RULES": "rules", "Rule": "rules", "render_rule_table": "rules",
    "EntryAudit": "hlo", "audit_entry_points": "hlo",
    "run_hlo_check": "hlo", "peak_live_bytes": "hlo",
    "write_hlo_baseline": "hlo",
    "ShardingAudit": "sharding", "audit_sharding": "sharding",
    "run_sharding_check": "sharding",
    "write_sharding_baseline": "sharding",
    "lint_concurrency_source": "concurrency",
    "lint_concurrency_paths": "concurrency",
    "run_concurrency_check": "concurrency",
    "write_concurrency_baseline": "concurrency",
    "lint_protocol_source": "protocol",
    "lint_protocol_paths": "protocol",
    "run_protocol_check": "protocol",
    "write_protocol_baseline": "protocol",
    "DeterministicScheduler": "schedule",
    "fleet_digest": "schedule", "schedule_sweep": "schedule",
}

__all__ = [
    "FLAGS", "Flag", "flag_bool", "flag_float", "flag_int", "flag_str",
    "render_flag_table", *_LAZY,
]


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{mod}", __name__), name)
