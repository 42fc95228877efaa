"""Runtime sanitizer: transfer guard + per-step recompile budget.

The two classic silent performance killers in a JAX train loop are
host<->device transfers inside the step (a sync per step) and
recompilation after warmup (a shape or flag leaking into the trace —
minutes lost per occurrence at scale).  Both are invisible to tests
that only check numerics.  ``sanitize()`` makes a smoke run FAIL on
either:

    with sanitize(recompile_budget=0, warmup_steps=1) as san:
        for i in range(steps):
            out = step(...)
            san.step()          # step boundary: budget enforced here

* transfers — wires ``jax.transfer_guard(level)`` for the body
  (default ``"disallow"``): JAX itself raises on implicit transfers.
* recompiles — flips ``jax_log_compiles`` and captures the
  "Finished XLA compilation of <name>" records from the
  ``jax._src.dispatch`` logger.  Compilations observed after
  ``warmup_steps`` completed step boundaries count against
  ``recompile_budget``; exceeding it raises
  :class:`RecompileBudgetExceeded` naming every offending computation.

:func:`sanitize_smoke` is the CI acceptance path (tools/ci.sh step 6):
it drives the standalone-GPT train step under
``sanitize(recompile_budget=0, warmup_steps=1)`` and proves the step
function compiles exactly once after warmup.
"""
from __future__ import annotations

import contextlib
import logging
import re
from typing import List, Optional

__all__ = ["RecompileBudgetExceeded", "Sanitizer", "sanitize",
           "sanitize_smoke"]

_COMPILE_RE = re.compile(r"Finished XLA compilation of (.+?) in")
_DISPATCH_LOGGER = "jax._src.dispatch"


class RecompileBudgetExceeded(RuntimeError):
    """A traced computation recompiled after warmup."""

    def __init__(self, names: List[str], budget: int, step: int):
        self.names = list(names)
        self.budget = budget
        self.step = step
        super().__init__(
            f"{len(names)} compilation(s) after warmup exceeded the "
            f"per-run recompile budget of {budget} at step boundary "
            f"{step}: {names} — a shape, python scalar, or env flag is "
            f"leaking into the trace")


class _CompileCapture(logging.Handler):
    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.names: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            self.names.append(m.group(1))


class Sanitizer:
    """Collects compile events between :meth:`step` boundaries; see
    module docstring.  Not a context manager itself — use
    :func:`sanitize`."""

    def __init__(self, *, recompile_budget: int = 0,
                 warmup_steps: int = 1) -> None:
        self.recompile_budget = int(recompile_budget)
        self.warmup_steps = int(warmup_steps)
        self.steps_done = 0
        self.warmup_compiles: List[str] = []
        self.post_warmup_compiles: List[str] = []
        self._capture = _CompileCapture()

    # wired by sanitize()
    def _drain(self) -> List[str]:
        names, self._capture.names = self._capture.names, []
        return names

    def step(self) -> None:
        """Mark a completed train step.  After ``warmup_steps`` of
        these, any captured compilation is charged against the budget
        and the step that overflows it raises."""
        names = self._drain()
        if self.steps_done < self.warmup_steps:
            self.warmup_compiles.extend(names)
        else:
            self.post_warmup_compiles.extend(names)
        self.steps_done += 1
        if len(self.post_warmup_compiles) > self.recompile_budget:
            raise RecompileBudgetExceeded(
                self.post_warmup_compiles, self.recompile_budget,
                self.steps_done)

    def finish(self) -> None:
        """Final boundary check (for loops that end right after the
        offending step) — called automatically on context exit.
        Events drained here belong to step ``steps_done + 1``, which is
        post-warmup whenever ``steps_done >= warmup_steps``."""
        names = self._drain()
        if self.steps_done < self.warmup_steps:
            self.warmup_compiles.extend(names)
            return
        self.post_warmup_compiles.extend(names)
        if len(self.post_warmup_compiles) > self.recompile_budget:
            raise RecompileBudgetExceeded(
                self.post_warmup_compiles, self.recompile_budget,
                self.steps_done)


@contextlib.contextmanager
def sanitize(*, transfer_guard: Optional[str] = "disallow",
             transfer_scope: str = "all",
             recompile_budget: int = 0, warmup_steps: int = 1):
    """Context manager yielding a :class:`Sanitizer`.

    ``transfer_guard``: a ``jax.transfer_guard`` level ("allow",
    "log", "disallow", ...) or None to leave transfers unguarded.
    ``transfer_scope``: "all" guards every direction;
    "device_to_host" guards only d→h — the deferred-telemetry proof
    (monitor.tracing.DeviceMetricsBuffer): under ``disallow`` the
    ring's one explicit ``jax.device_get`` drain is permitted while
    any implicit per-step readback (``float(loss)``, ``np.asarray``)
    raises, so a passing run *is* the zero-per-step-transfer claim.
    ``recompile_budget``/``warmup_steps``: see :class:`Sanitizer`.
    """
    import jax

    if transfer_scope not in ("all", "device_to_host"):
        raise ValueError(f"unknown transfer_scope {transfer_scope!r} "
                         "(use 'all' or 'device_to_host')")
    san = Sanitizer(recompile_budget=recompile_budget,
                    warmup_steps=warmup_steps)
    logger = logging.getLogger(_DISPATCH_LOGGER)
    prior_level = logger.level
    prior_propagate = logger.propagate
    logger.addHandler(san._capture)
    # log_compiles emits at WARNING via this logger; make sure the
    # records reach handlers even if the app raised the level, and
    # keep them out of the user's console while we capture
    if logger.level > logging.WARNING:
        logger.setLevel(logging.WARNING)
    logger.propagate = False
    # pxla chats "Compiling <name> with global shapes" on the same
    # flag; silence it for the duration too
    pxla_logger = logging.getLogger("jax._src.interpreters.pxla")
    prior_pxla_propagate = pxla_logger.propagate
    pxla_logger.propagate = False
    pxla_null = logging.NullHandler()  # else logging.lastResort prints
    pxla_logger.addHandler(pxla_null)
    prior_flag = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        if transfer_guard is not None:
            guard = (jax.transfer_guard_device_to_host
                     if transfer_scope == "device_to_host"
                     else jax.transfer_guard)
            with guard(transfer_guard):
                yield san
        else:
            yield san
        san.finish()
    finally:
        jax.config.update("jax_log_compiles", prior_flag)
        logger.removeHandler(san._capture)
        logger.setLevel(prior_level)
        logger.propagate = prior_propagate
        pxla_logger.removeHandler(pxla_null)
        pxla_logger.propagate = prior_pxla_propagate


def sanitize_smoke(steps: int = 4, *, scan_steps: int = 0,
                   verbose: bool = True) -> int:
    """Drive the standalone-GPT smoke step under the sanitizer; the CI
    proof that the train step compiles exactly once after warmup.

    Returns the number of post-warmup recompiles (0 on success);
    raises :class:`RecompileBudgetExceeded` on any.  The model and
    step come from the SAME construction path the train-smoke loop and
    the hlo auditor use (``testing.standalone_gpt.make_smoke_setup`` /
    ``build_train_step`` — the shared entry-point list), so this smoke
    proves the exact step CI audits.

    ``scan_steps`` >= 1 drives the batched-step scan driver instead
    (``build_train_step_scan``, the ``gpt_train_step_scan`` audit
    entry): ``steps`` K-step windows per run, one ``san.step()``
    boundary per window — proving an N-step run (N = steps*K) costs
    exactly ONE compile after warmup, the scan half of ROADMAP item
    2's dispatch-amortization claim.
    """
    from ..testing.standalone_gpt import (build_train_step,
                                          build_train_step_scan,
                                          make_smoke_setup)

    setup = make_smoke_setup(opt_level="O2")
    if scan_steps and scan_steps > 0:
        step = build_train_step_scan(setup, scan_steps)
    else:
        step = build_train_step(setup)
    params, amp_state = setup.params, setup.amp_state

    # the init/initialize compiles above happen OUTSIDE the sanitizer;
    # transfer_guard stays off for the smoke (loss readout is an
    # explicit, expected device->host transfer)
    with sanitize(transfer_guard=None, recompile_budget=0,
                  warmup_steps=1) as san:
        for _ in range(steps):
            params, amp_state, loss, _, _ = step(params, amp_state)
            loss.block_until_ready()
            san.step()
    if verbose:
        total = steps * max(1, scan_steps)
        print(f"[sanitize-smoke] steps={total}"
              + (f" (scan K={scan_steps}, {steps} windows)"
                 if scan_steps else "")
              + f" warmup_compiles={len(san.warmup_compiles)} "
              f"post_warmup_compiles={len(san.post_warmup_compiles)} "
              f"loss={float(loss):.4f}")
    return len(san.post_warmup_compiles)
