"""Persistent XLA compilation-cache wiring (ROADMAP item 2, ISSUE-8).

Compile cost is the other half of the wall-vs-device gap: the scan
driver amortizes per-step dispatch, but every *process* still pays the
full XLA compile of each entry point it touches — minutes of apparent
"wall" on a cold host that have nothing to do with the step being
measured.  JAX's persistent compilation cache keys a lowered module to
a disk entry; :func:`configure_compile_cache` decides ONCE where that
cache lives (see its precedence) and relaxes the min-size/
min-compile-time floors so even smoke-sized programs are cached —
exactly the programs CI and the drivers recompile most often.

One ``python -m apex_tpu.testing.entry_points --aot`` run per host
pre-populates the cache for every registered entry point
(``jit(...).lower().compile()`` — no execution); every later process
warm-starts from disk.  tests/test_scan_driver.py proves the
second-process hit with jax's own compile/cache-hit log records.
"""
from __future__ import annotations

import os
from typing import Optional

from ..analysis.flags import flag_str
from .log_util import get_logger

__all__ = ["configure_compile_cache"]

logger = get_logger(__name__)

_configured: Optional[str] = None


# The cache key includes the directory's path, so the on-chip default is
# one fixed place under the checkout (git-ignored), never a temp name.
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache and return the
    directory in effect (None: no cache).  In order:

    1. ``JAX_COMPILATION_CACHE_DIR`` is set: jax's own setting stands —
       no directory is set in code;
    2. the ``APEX_TPU_COMPILE_CACHE_DIR`` flag;
    3. on a TPU backend, ``<checkout>/.jax_cache`` — a cold chip run
       then shares its compiles between phases and with the next run
       from the same checkout;
    4. otherwise none.  The platform condition is deliberate: tier-1
       calls the smoke drivers hundreds of times on the CPU, and a
       default cache there would fill the checkout with entries.

    Idempotent.  The min-entry-size and min-compile-time floors are
    relaxed so the smoke/test-tier programs (fast compiles, small
    modules) are cached too, and the cache key includes the ops'
    metadata, so an executable loaded from the cache carries the
    names the program has now.
    """
    global _configured
    import jax

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")  # apex-lint: disable=APX301 -- jax's own variable, read only to stand aside for it; not an APEX_TPU flag
    directory = from_env or flag_str("APEX_TPU_COMPILE_CACHE_DIR")
    if not directory and jax.default_backend() == "tpu":
        directory = _CHECKOUT_CACHE
    if not directory:
        return None
    if _configured == directory:
        return directory
    if not from_env:
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    # An executable's op names (``jax.named_scope``s, flax module
    # paths) are what a device trace attributes time by, and jax's
    # default key strips them: a program that differs from a cached one
    # in names alone would be handed the cached executable with the OLD
    # names, and ``apex.optimizer`` / ``apex.head_loss`` would be
    # missing from its profile.  With the metadata in the key a change
    # of names (or of a traced line's number) compiles once more.
    for name, val in (
            ("jax_persistent_cache_min_compile_time_secs", 0.0),
            ("jax_persistent_cache_min_entry_size_bytes", -1),
            ("jax_compilation_cache_include_metadata_in_key", True)):
        jax.config.update(name, val)
    # jax initializes the cache AT MOST ONCE, on the first compile: if
    # any compile ran before this call (or the dir changed), the
    # latched no-cache/old-dir state silently wins and every later
    # config.update is a no-op.  Reset so the next compile re-reads
    # the directory.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()
    if _configured is not None:
        logger.info("compile cache re-pointed: %s -> %s", _configured,
                    directory)
    _configured = directory
    return directory
