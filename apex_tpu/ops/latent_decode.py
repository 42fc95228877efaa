"""Pallas latent decode: paged attention over a **latent** cache
(multi-head latent attention, MLA, in its absorbed form).

The cache holds one row a token and layer, ``[c_kv ; k_rope]`` (512 + 64
values at the served model), shared by every query head: there are no
cache heads, and there is no v array, a row's first ``value_dim`` values
being its value.  A query arrives *absorbed* (``q_nope`` already through
``W_uk``, so it scores against ``c_kv`` directly) as ``h`` rows of the
latent's width, and leaves as ``h`` rows of ``value_dim``, which the
caller takes through ``W_uv``.  So a page is fetched from HBM **once**
and used twice: all ``h`` query heads against its rows in one matmul,
and the softmax's weights against the same rows' first ``value_dim``
lanes in another.  At ``2 x h x (576 + 512)`` flops for 1,152 bytes a
cached token, ``h`` = 128 puts the kernel at 242 flop/byte, on a v5e's
ridge: the page has to arrive at full bandwidth AND the matmuls have to
be full tiles.

Layouts (``bs`` tokens a cache block, ``dl`` the width a row is stored
at: the latent's, filled with zeros to whole 128-lane tiles, 576 -> 640,
and the query filled alike; see :attr:`~apex_tpu.serving.mla_moe.
MlaSpec.row_dim`):

* q            (b, h, dl)  -- one absorbed query token a sequence
* cache        (nb, 1, bs, dl)  -- :class:`~apex_tpu.serving.kv_cache.
  KVCacheConfig`'s latent kind, as it lies
* block_tables (b, max_pages) int32, seq_lens (b,) int32: as
  :func:`~.flash_decode.flash_decode` has them (block 0 pads, a length
  of 0 marks an inactive row whose output is exactly 0).

The grid is ``(batch row, head chunk, page group)``.  A step carries
``P`` **pages** (:func:`_pages_per_step`: as many as make 128 key
positions, 8 at the served block of 16): the cache is passed ``P`` times,
each operand's index map reading its own entry of the block table, so
the pipeline gathers ``P`` scattered pages a step and the score tile is
``(h, P x bs)`` -- a full MXU tile where a page a step would fill an
eighth of one and pay the grid's fixed cost eight times as often.  Pages
past a row's length point at the dump page and are not fetched again;
a group wholly past it is skipped.  Online softmax runs across the
groups as in :mod:`.flash_decode`, fp32, exp2 with the scale folded in.

The ``t``-token form (:func:`latent_decode_multi`: speculation's verify
step, chunked extend) is the same program with ``t`` query rows a head,
causal within the ``t``; as ``h x t`` grows the head axis is taken apart
(:func:`_heads_per_step`) to keep a step inside a stated VMEM budget,
each chunk of heads reading the pages again.

Inference-only.  The jnp twins are :func:`latent_attention_reference`
and :func:`latent_attention_multi_reference`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _LOG2E, _NEG, _dot, _interpret

__all__ = ["latent_decode", "latent_decode_multi",
           "latent_attention_reference",
           "latent_attention_multi_reference"]

# What one grid step's working set may take of VMEM (as
# flash_decode._STEP_VMEM_BYTES: well inside the 16 MiB scoped default).
_STEP_VMEM_BYTES = 6 * 1024 * 1024
# key positions a step scores at once: one MXU tile's columns
_STEP_KEYS = 128


def _pages_per_step(max_pages: int, bs: int) -> int:
    """Pages a grid step carries: the largest divisor of the page rung
    that makes at most :data:`_STEP_KEYS` key positions."""
    most = max(1, _STEP_KEYS // bs)
    return max(p for p in range(1, most + 1) if max_pages % p == 0)


def _heads_per_step(h: int, t: int, dl: int, dv: int) -> int:
    """Query heads a grid step carries: every one unless the rows'
    working set (q and o double-buffered, the m/l/acc carries, four fp32
    temporaries of a score tile's or the value's width) would pass
    :data:`_STEP_VMEM_BYTES`; then the largest divisor of ``h`` that
    does not and keeps ``hg * t`` a multiple of 8 (the q block is ``(hg
    * t, dl)`` of a flat ``(h * t, dl)``), or the smallest such divisor
    where none fits.  Read from the shapes alone, never from the batch
    or page rung."""
    row = 2 * 2 * dl + 2 * 2 * dv + (2 * 128 + dv) * 4 \
        + 4 * max(_STEP_KEYS, dv) * 4
    ok = [g for g in range(1, h + 1)
          if h % g == 0 and (g == h or g * t % 8 == 0)]
    fits = [g for g in ok if g * t * row <= _STEP_VMEM_BYTES]
    return max(fits) if fits else min(ok)


def _latent_kernel(a, bs, t, pages, dv, *refs):
    """One (batch row, head chunk, page group) program: ``hg`` heads of
    ``t`` query rows (row ``r`` is query ``r % t`` of head ``r // t``)
    against ``pages`` pages of the row's latent cache.  Scalar-prefetch
    refs lead: the block table (read by the index maps) and seq_lens."""
    bt_ref, sl_ref, q_ref, *rest = refs
    page_refs, (o_ref, m_sc, l_sc, acc) = rest[:pages], rest[pages:]
    b = pl.program_id(0)
    j = pl.program_id(2)
    sl = sl_ref[b]
    first = j * (pages * bs)           # this group's first key position

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    @pl.when(first < sl)
    def _group():
        q = q_ref[0]                                   # (hg*t, dl)
        rows = [r[0, 0] for r in page_refs]            # each (bs, dl)
        k = rows[0] if pages == 1 else jnp.concatenate(rows, axis=0)
        s = _dot(q, k, trans_b=True)                   # (hg*t, P*bs) fp32
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        query = 0 if t == 1 else row - jax.lax.div(row, t) * t
        live = first + col <= sl - t + query
        s = jnp.where(live, s, _NEG)
        m_prev = m_sc[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp2((m_prev - m_cur) * a)
        # a front-padding row (negative position) has every key masked:
        # zero p explicitly so that it sums to l = 0 and emits exactly 0
        p = jnp.where(live, jnp.exp2((s - m_cur) * a), 0.0)
        l_sc[:, :1] = l_sc[:, :1] * corr + jnp.sum(p, axis=1,
                                                   keepdims=True)
        m_sc[:, :1] = m_cur
        acc[:] = acc[:] * corr + _dot(p.astype(k.dtype), k[:, :dv])

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = l_sc[:, :1]
        dead = l == 0.0
        out = jnp.where(dead, 0.0, acc[:] / jnp.where(dead, 1.0, l)) \
            .astype(o_ref.dtype)
        for hh in range(o_ref.shape[1]):
            o_ref[0, hh] = out[hh * t:(hh + 1) * t]


@functools.partial(jax.jit, static_argnames=("value_dim", "scale", "name",
                                             "interpret"))
def _latent_paged(q4, cache, block_tables, seq_lens, value_dim, scale,
                  name, interpret):
    """The pallas_call driver: q4 (b, h, t, dl) -> (b, h, t, value_dim).
    Jitted so that a step's layers, which call it with the same shapes,
    lower it once."""
    b, h, t, dl = q4.shape
    nb, _, bs, _ = cache.shape
    mp = block_tables.shape[1]
    pages = _pages_per_step(mp, bs)
    hg = _heads_per_step(h, t, dl, value_dim)

    def page_spec(p):
        return pl.BlockSpec(
            (1, 1, bs, dl),
            lambda b_, h_, j, bt, sl: (bt[b_, j * pages + p], 0, 0, 0),
            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hg, mp // pages),
        in_specs=[pl.BlockSpec((1, hg * t, dl),
                               lambda b_, h_, j, bt, sl: (b_, h_, 0),
                               memory_space=pltpu.VMEM)]
        + [page_spec(p) for p in range(pages)],
        out_specs=pl.BlockSpec((1, hg, t, value_dim),
                               lambda b_, h_, j, bt, sl: (b_, h_, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((hg * t, 128), jnp.float32),
                        pltpu.VMEM((hg * t, 128), jnp.float32),
                        pltpu.VMEM((hg * t, value_dim), jnp.float32)])
    kernel = functools.partial(_latent_kernel, scale * _LOG2E, bs, t,
                               pages, value_dim)
    return pl.pallas_call(
        kernel, name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, value_dim), q4.dtype),
        interpret=interpret)(
            block_tables, seq_lens, q4.reshape(b, h * t, dl),
            *[cache] * pages)


def _check(q, cache, value_dim):
    dl = q.shape[-1]
    if cache.ndim != 4 or cache.shape[1] != 1 or cache.shape[3] != dl:
        raise ValueError(
            f"a latent cache is (blocks, 1, block, {dl}) for q "
            f"{q.shape}, not {cache.shape}")
    if not 0 < value_dim <= dl:
        raise ValueError(f"value_dim {value_dim} outside (0, {dl}]")


def latent_decode(q: jnp.ndarray, cache: jnp.ndarray,
                  block_tables: jnp.ndarray, seq_lens: jnp.ndarray, *,
                  value_dim: int,
                  scale: Optional[float] = None) -> jnp.ndarray:
    """Single-query attention of ``h`` absorbed heads over a paged
    latent cache: ``q`` (b, h, dl), ``cache`` (nb, 1, bs, dl) ->
    (b, h, value_dim) in q's dtype, ``softmax(q . row * scale)`` over
    the row's first ``seq_lens[b]`` positions times those rows' first
    ``value_dim`` values.  ``scale`` is the model's (MLA: one over the
    root of the *expanded* head's width, 192), defaulting to ``dl **
    -0.5``.  The call keeps the form a device trace is read by: output
    ``(b, h, 1, value_dim)``, first operand the block table."""
    _check(q, cache, value_dim)
    out = _latent_paged(
        q[:, :, None, :], cache, block_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32), value_dim,
        q.shape[-1] ** -0.5 if scale is None else scale,
        "paged_latent_decode", _interpret())
    return out[:, :, 0, :]


def latent_decode_multi(q: jnp.ndarray, cache: jnp.ndarray,
                        block_tables: jnp.ndarray, seq_lens: jnp.ndarray,
                        *, value_dim: int,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """``t`` contiguous query tokens a sequence: ``q`` (b, t, h, dl) ->
    (b, t, h, value_dim).  Row ``r`` sits at position ``seq_lens[b] - t
    + r`` and attends to positions up to its own, which the step has
    written before it attends (:func:`~.flash_decode.
    flash_decode_multi`'s rule); rows at negative positions and rows of
    an inactive sequence emit exactly 0."""
    _check(q, cache, value_dim)
    out = _latent_paged(
        q.transpose(0, 2, 1, 3), cache, block_tables.astype(jnp.int32),
        seq_lens.astype(jnp.int32), value_dim,
        q.shape[-1] ** -0.5 if scale is None else scale,
        "paged_latent_decode_multi", _interpret())
    return out.transpose(0, 2, 1, 3)


# --- jnp twins ---------------------------------------------------------------

def latent_attention_multi_reference(q, cache, block_tables, seq_lens, *,
                                     value_dim, scale=None):
    """Dense jnp twin of :func:`latent_decode_multi`: gather every
    row's pages, mask by the contiguous chunk's causal rule, fp32
    softmax."""
    b, t, h, dl = q.shape
    if scale is None:
        scale = dl ** -0.5
    bs = cache.shape[2]
    mp = block_tables.shape[1]
    rows = cache[block_tables][:, :, 0].reshape(b, mp * bs, dl) \
        .astype(jnp.float32)
    s = jnp.einsum("bthd,bkd->bthk", q.astype(jnp.float32), rows) * scale
    pos = jnp.arange(mp * bs, dtype=jnp.int32)[None, None, None, :]
    qpos = seq_lens.astype(jnp.int32)[:, None] - t \
        + jnp.arange(t, dtype=jnp.int32)[None, :]
    mask = pos <= qpos[:, :, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)),
                  0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bthk,bkd->bthd", p / jnp.where(l == 0.0, 1.0, l),
                   rows[..., :value_dim])
    return jnp.where(l == 0.0, 0.0, o).astype(q.dtype)


def latent_attention_reference(q, cache, block_tables, seq_lens, *,
                               value_dim, scale=None):
    """Dense jnp twin of :func:`latent_decode` (the ``t == 1`` chunk)."""
    return latent_attention_multi_reference(
        q[:, None], cache, block_tables, seq_lens, value_dim=value_dim,
        scale=scale)[:, 0]
