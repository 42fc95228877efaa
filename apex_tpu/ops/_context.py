"""Execution-context helpers shared by the Pallas op wrappers."""
from __future__ import annotations

from .._compat import typeof


def in_manual_axis_context(*operands) -> bool:
    """True when the computation is inside ``shard_map`` manual axes.

    Pallas calls cannot yet express varying-mesh-axis (VMA) types on
    their outputs, so inside ``shard_map(check_vma=True)`` every fused op
    routes to its XLA-fusion reference implementation — same math, XLA
    still fuses it per shard.  Outside (plain jit / pjit / GSPMD) the
    Pallas kernels run.

    The public ``jax.typeof(operand).vma`` type gives a fast positive
    (any varying operand => manual context); the axis-env probe then
    decides the rest.  The axis env CANNOT be skipped even when every
    operand is unvarying: ``pallas_call`` inside
    ``shard_map(check_vma=True)`` demands vma-typed out specs regardless
    of operand variance, so replicated inputs still need the fallback.
    Deliberate trade-off: this also routes ``vmap(axis_name=...)``
    bodies (where the Pallas call would be legal) to the fallback —
    named-axis vmap is rare and the fallback is merely the XLA-fused
    reference implementation; choosing correctness under shard_map over
    that corner's kernel dispatch.
    The axis-env probe is deliberately NOT wrapped in a blanket except —
    if the private API drifts, failing loudly here beats silently
    running a Pallas call that check_vma rejects later.
    """
    for x in operands:
        try:
            if typeof(x).vma:
                return True
        except (AttributeError, TypeError):
            continue
    from jax._src import core as _jax_core

    return bool(_jax_core.get_axis_env().axis_sizes)
