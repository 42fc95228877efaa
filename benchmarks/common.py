"""What both kinds of run share: the result a kind hands back, the
count of programs lowered inside a window, the ``[bench]`` lines."""
from __future__ import annotations

import dataclasses
import sys
from typing import Any, Dict, List, Optional

from jax import monitoring

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclasses.dataclass
class CellResult:
    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]      # every end-to-end metric the kind
    #                                   measures, setup_s excepted
    window_opened_at: float           # time.perf_counter() instant
    facts: Dict[str, Any]             # host-side records for the readers
    trace: Optional[Any] = None       # benchmarks.trace.Trace
    faults: List[str] = dataclasses.field(default_factory=list)


class CompileCounter:
    """Counts programs jax lowers (every compile, and every look-up in
    the persistent cache, starts with a lowering) from ``__enter__`` on,
    in ``count``."""

    def __init__(self):
        self.count = 0

    def _on_event(self, name, duration, **kwargs):
        if name == _LOWERED:
            self.count += 1

    def __enter__(self):
        monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_duration_listener(self._on_event)
        return False


def say(**facts) -> None:
    """One ``[bench] key=value ...`` line on standard output."""
    print("[bench] " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def check(result_faults: List[str], ok: bool, what: str) -> None:
    """Record a failed correctness check (the run still ends and
    prints ``correct: false`` with the reasons on a [bench] line)."""
    if not ok:
        result_faults.append(what)
        print(f"[bench] FAULT {what}", file=sys.stderr, flush=True)
