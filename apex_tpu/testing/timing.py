"""Honest TPU timing helpers.

Dispatch is asynchronous, so a wall-clock loop that does not wait for
the result times the enqueue.  These helpers force completion by
fetching a scalar value from the result, and amortize the fetch
round-trip over chained dependent iterations (each call consumes the
previous call's output, preventing dedup/caching of identical
executions).
"""
from __future__ import annotations

import time
from typing import Callable

import jax
import jax.numpy as jnp


def _fetch(x):
    leaf = jax.tree_util.tree_leaves(x)[0]
    return float(jnp.ravel(leaf)[0].astype(jnp.float32))


def bench_chained(step: Callable, init_carry, n: int = 20,
                  warmup: int = 2) -> float:
    """Return seconds/iteration of ``carry = step(carry)`` with a forced
    value fetch at the end.  ``step`` must map carry -> carry."""
    carry = init_carry
    for _ in range(warmup):
        carry = step(carry)
    _fetch(carry)
    carry = init_carry
    t0 = time.time()
    for _ in range(n):
        carry = step(carry)
    _fetch(carry)
    return (time.time() - t0) / n
