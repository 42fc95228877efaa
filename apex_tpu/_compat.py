"""The one place apex_tpu names the jax sharding surface it depends on.

Every apex_tpu module (and the repo's tests/benches) imports
``shard_map``, ``typeof``, ``axis_size``, ``axis_index``, ``pcast`` and
``set_mesh`` from here instead of touching ``jax.shard_map`` directly —
the trace-safety linter enforces it (rule APX501) — so the day jax
renames one of them there is one line to change.  The names are plain
aliases of the installed API: the repo supports the jax it is tested
with and carries no branch for any other.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "typeof", "axis_size", "axis_index", "pcast",
           "set_mesh", "psum_replicated"]

shard_map = jax.shard_map
typeof = jax.typeof
set_mesh = jax.set_mesh
axis_size = jax.lax.axis_size
axis_index = jax.lax.axis_index
pcast = jax.lax.pcast
# The replicate-a-masked-buffer idiom (one rank holds the data, the rest
# hold zeros): under jax's varying-axis types the cotangent of the
# replicated psum output seeds once, so plain psum differentiates
# correctly inside shard_map.
psum_replicated = jax.lax.psum
