"""Pallas flash-decode: single-query attention against a block-paged
KV cache (the serving counterpart of :mod:`.flash_attention`).

Decode-time attention is the degenerate q-dimension case of flash
attention: one query row per sequence, attending over everything that
sequence has generated so far.  The KV history lives in a **paged**
cache — fixed-size blocks owned by a free-list pool
(:class:`apex_tpu.serving.KVCacheManager`), so admitting or evicting a
request never moves another request's bytes — and the kernel gathers a
sequence's pages through its **block table** with a scalar-prefetched
index map: page ``j`` of batch row ``b`` is fetched from cache block
``block_tables[b, j]`` directly by the Pallas pipeline, no materialized
(b, pages, bs, d) copy anywhere (the naive decode baseline bench.py's
``serving`` section measures against does exactly that copy).

Layouts (``bs`` = tokens per cache block, the APEX_TPU_SERVE_KV_BLOCK
grain):

* q            (b, h, d)         — one query token per sequence
* k/v cache    (nb, hk, bs, dk)  — block-major; ``hk``/``dk`` are the
  STORAGE head axes: ``(h, d)`` unpacked, ``(h/2, 2d)`` head-packed
* block_tables (b, max_pages) int32 — cache-block id per page; pages
  past a sequence's length point at block 0 (the reserved dump page)
* seq_lens     (b,) int32        — attend over positions < seq_len;
  0 marks an inactive batch row (output is exactly 0)

Head packing at d=64 reuses the PR-1 sign-rotation trick
(:mod:`.flash_attention` module note) and is FREE at decode time: with
one token per step, packing adjacent head pairs onto one 128-lane tile
is a plain reshape ``(h, 64) -> (h/2, 128)`` — no transpose, because
the degenerate q dimension is exactly the axis the training-side pack
had to move.  The cache is *stored* packed (the manager's layout), the
per-step append is a reshape, and every matmul runs full-width: the
scores come from the same half-sum/half-difference rotation
(:func:`flash_attention._packed_scores`), the output from the mirrored
combine.  ``APEX_TPU_FLASH_PACK_D64=0`` forces the half-width layout
end to end (cache layout and kernel agree by construction — both ask
:func:`use_decode_head_packing`).

Online softmax runs across pages exactly as the training forward runs
across k-blocks: per-(batch, head-group) scratch carries m/l/acc over
the page grid dimension, pages wholly past ``seq_len`` are skipped via
``pl.when``, and the straddling page masks by global position.  Softmax
math is fp32 with the exp2 pre-folded constants.

Int8 KV (weight-only storage; APEX_TPU_SERVE_KV_DTYPE=int8): k/v store
as int8 with **per-row** (per cached token, per head) fp32 scales, so
appending a token never requantizes history; the kernel dequantizes
each page block in-VMEM before the matmuls.  Scales ride their own
``(nb, h, bs)`` arrays and are gathered through the same block table.

Inference-only: no VJP is defined (decode never differentiates).

The jnp twin is :func:`paged_attention_reference` — the CPU oracle the
parity audit (APX401/402) pins this kernel to and the dense math the
serving tests diff against.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_LOG2E, _NEG, _dot, _interpret,
                              _packed_out, _packed_scores,
                              _pack_lane_cols, _use_head_packing)

__all__ = ["flash_decode", "flash_decode_multi",
           "paged_attention_reference",
           "paged_attention_multi_reference",
           "use_decode_head_packing", "pack_decode_heads",
           "unpack_decode_heads", "dequantize_kv"]


def use_decode_head_packing(h: int, d: int) -> bool:
    """Whether decode (and therefore the CACHE LAYOUT — the two must
    agree) packs d=64 head pairs onto 128 lanes; same predicate and
    escape hatch (``APEX_TPU_FLASH_PACK_D64`` /
    ``flash_attention.set_head_packing``) as the training kernels."""
    return _use_head_packing(h, d)


def pack_decode_heads(x: jnp.ndarray) -> jnp.ndarray:
    """(..., h, d) -> (..., h/2, 2d): adjacent head pairs share a lane
    tile.  For single-token decode rows this is a pure reshape (the
    packed lane axis is contiguous in memory) — the reason packing is
    free at decode time where the training pack needed a transpose."""
    *lead, h, d = x.shape
    return x.reshape(*lead, h // 2, 2 * d)


def unpack_decode_heads(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_decode_heads`."""
    *lead, hp, d2 = x.shape
    return x.reshape(*lead, hp * 2, d2 // 2)


def _pos_mask(shape, page0, sl):
    """cols are global positions [page0, page0 + bs); True = attend."""
    pos = page0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return pos < sl


def _row_scales(s_ref, pack, width):
    """This page's per-row dequant factors as a (bs, 1) column — or,
    packed, a (bs, width) array with each head's factor on its lane
    half.  ``s_ref`` is the (1, 1, g, bs) block of the
    (nb, hk, g, bs) scale view."""
    if pack:
        return _pack_lane_cols(s_ref[0, 0, 0, :][:, None],
                               s_ref[0, 0, 1, :][:, None], width)
    return s_ref[0, 0, 0, :][:, None]


def _normalized(l_sc, acc, pack):
    """acc / l with dead rows (l == 0: an inactive sequence or a
    front-padding row) emitting exactly 0.  Packed, l is spread to the
    lane halves first and the dead test runs on that float array —
    Mosaic has no lane select over booleans."""
    l = _pack_lane_cols(l_sc[:, :1], l_sc[:, 1:2], acc.shape[1]) \
        if pack else l_sc[:, :1]
    dead = l == 0.0
    return jnp.where(dead, 0.0, acc[:] / jnp.where(dead, 1.0, l))


def _scale_operand(scale, hk, g):
    """(nb, h, bs) global-head-order scales viewed (nb, hk, g, bs): a
    program's head group becomes a whole trailing (g, bs) block, which
    the TPU lowering accepts where a size-g block on an axis of h is
    refused."""
    nb, _, bs = scale.shape
    return scale.reshape(nb, hk, g, bs)


def _decode_kernel(a, bs, pack, has_scale, *refs):
    """One (batch row, head group, page) program.  Scalar-prefetch refs
    lead: block tables (consumed by the index maps, unused here) and
    seq_lens.  Scratch m/l ride columns 0..g-1 of a (1, 128) carry —
    the training kernels' column-per-head idiom at bq=1."""
    bt_ref, sl_ref, q_ref, k_ref, v_ref, *rest = refs
    if has_scale:
        ks_ref, vs_ref, *rest = rest
    o_ref, m_sc, l_sc, acc = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    sl = sl_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    # a page wholly past the sequence contributes nothing — skip it
    # (its block-table entry points at the dump page; the DMA is the
    # bucketed cost the ladder accounts for, the FLOPs are not paid)
    @pl.when(j * bs < sl)
    def _page():
        q = q_ref[0, 0]                               # (1, dk)
        k = k_ref[0, 0]                               # (bs, dk)
        v = v_ref[0, 0]
        if has_scale:
            # int8 rows -> f32 in VMEM; per-row scales so history is
            # never requantized by an append.  Packed: each lane half
            # is one head's row, scaled by that head's factor.
            k = k.astype(jnp.float32) * _row_scales(ks_ref, pack,
                                                    k.shape[-1])
            v = v.astype(jnp.float32) * _row_scales(vs_ref, pack,
                                                    v.shape[-1])
        heads = _packed_scores(q, k) if pack \
            else (_dot(q, k, trans_b=True),)           # (1, bs) fp32
        mask = _pos_mask(heads[0].shape, j * bs, sl)
        pas, corrs = [], []
        for hh, s in enumerate(heads):
            s = jnp.where(mask, s, _NEG)
            m_prev = m_sc[:, hh:hh + 1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1,
                                                keepdims=True))
            corr = jnp.exp2((m_prev - m_cur) * a)
            p = jnp.exp2((s - m_cur) * a)
            # the straddling page's masked tail: (s - m_cur) = 0 there
            # when every column so far is masked — zero p explicitly
            # so dead rows sum to l = 0 and emit exactly 0
            p = jnp.where(mask, p, 0.0)
            l_sc[:, hh:hh + 1] = l_sc[:, hh:hh + 1] * corr \
                + jnp.sum(p, axis=1, keepdims=True)
            m_sc[:, hh:hh + 1] = m_cur
            pas.append(p)
            corrs.append(corr)
        if pack:
            corr_w = _pack_lane_cols(corrs[0], corrs[1], acc.shape[1])
            acc[:] = acc[:] * corr_w + _packed_out(pas[0], pas[1], v)
        else:
            acc[:] = acc[:] * corrs[0] \
                + _dot(pas[0].astype(v.dtype), v)

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0, 0] = _normalized(l_sc, acc, pack).astype(o_ref.dtype)


def _decode_paged(q3, k_cache, v_cache, block_tables, seq_lens, scale,
                  k_scale, v_scale, pack):
    """The pallas_call driver: grid (b, head groups, pages), block
    tables + seq_lens scalar-prefetched so the k/v index maps read the
    page id directly — the gather IS the pipeline's block fetch."""
    b, hk, dk = q3.shape
    nb, _, bs, _ = k_cache.shape
    mp = block_tables.shape[1]
    a = float(scale) * _LOG2E
    has_scale = k_scale is not None
    g = 2 if pack else 1

    # q/o ride as (b, hk, 1, dk): the block's trailing (1, dk) is then
    # the array's own — a block of 1 on the hk axis of (b, hk, dk) is
    # refused by the TPU lowering
    def qo_spec():
        return pl.BlockSpec((1, 1, 1, dk),
                            lambda b_, h_, j, bt, sl: (b_, h_, 0, 0),
                            memory_space=pltpu.VMEM)

    kv_spec = pl.BlockSpec(
        (1, 1, bs, dk),
        lambda b_, h_, j, bt, sl: (bt[b_, j], h_, 0, 0),
        memory_space=pltpu.VMEM)
    in_specs = [qo_spec(), kv_spec, kv_spec]
    operands = [q3[:, :, None, :], k_cache, v_cache]
    if has_scale:
        sc_spec = pl.BlockSpec(
            (1, 1, g, bs),
            lambda b_, h_, j, bt, sl: (bt[b_, j], h_, 0, 0),
            memory_space=pltpu.VMEM)
        in_specs += [sc_spec, sc_spec]
        operands += [_scale_operand(k_scale, hk, g),
                     _scale_operand(v_scale, hk, g)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, mp),
        in_specs=in_specs,
        out_specs=qo_spec(),
        scratch_shapes=[
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
        ])
    return pl.pallas_call(
        functools.partial(_decode_kernel, a, bs, pack, has_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, 1, dk), q3.dtype),
        name="paged_flash_decode",
        interpret=_interpret(),
    )(block_tables, seq_lens, *operands)[:, :, 0, :]


def flash_decode(q: jnp.ndarray, k_cache: jnp.ndarray,
                 v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                 seq_lens: jnp.ndarray, *,
                 scale: Optional[float] = None,
                 k_scale: Optional[jnp.ndarray] = None,
                 v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Single-query attention over a block-paged KV cache.

    ``q`` is (b, h, d) — one query token per sequence; the cache is
    (nb, hk, bs, dk) block-major (see the module note for the packed
    ``hk``/``dk`` convention — the cache layout decides the kernel
    path, so the pool that allocated it is the single source of
    truth).  ``block_tables`` (b, max_pages) int32 names each row's
    pages; ``seq_lens`` (b,) bounds the attended positions, 0 marking
    an inactive row (output exactly 0).  ``k_scale``/``v_scale``
    (nb, h, bs) fp32 arm the int8 weight-only dequant path.  Returns
    (b, h, d) in q's dtype.  Inference-only (no VJP).
    """
    b, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    nb, hk, bs, dk = k_cache.shape
    if v_cache.shape != k_cache.shape:
        raise ValueError(f"k/v cache shapes differ: {k_cache.shape} "
                         f"vs {v_cache.shape}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if hk == h and dk == d:
        pack = False
    elif h % 2 == 0 and hk == h // 2 and dk == 2 * d:
        pack = True
    else:
        raise ValueError(
            f"cache head layout {(hk, dk)} matches neither unpacked "
            f"{(h, d)} nor head-packed {(h // 2, 2 * d)} for q "
            f"{q.shape}")
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if sc is not None and sc.shape != (nb, h, bs):
            raise ValueError(f"{name} shape {sc.shape} != expected "
                             f"{(nb, h, bs)} (global head order)")
    q3 = pack_decode_heads(q) if pack else q
    out = _decode_paged(q3, k_cache, v_cache,
                        block_tables.astype(jnp.int32),
                        seq_lens.astype(jnp.int32), scale,
                        k_scale, v_scale, pack)
    return unpack_decode_heads(out) if pack else out


# --- multi-token path (speculative verify / chunked prefill) ---------------

def _decode_multi_kernel(a, bs, t, pack, has_scale, *refs):
    """One (batch row, head group, page) program over a CHUNK of ``t``
    query rows.  Row ``r`` of batch ``b`` sits at global position
    ``seq_lens[b] - t + r`` (chunk positions are contiguous and end at
    the last written slot), so the per-row causal mask is
    ``pos <= sl - t + r`` — at ``t == 1`` this is exactly the decode
    kernel's ``pos < sl``.  m/l scratch carries one row per query in
    columns 0..g-1; everything else mirrors :func:`_decode_kernel`."""
    bt_ref, sl_ref, q_ref, k_ref, v_ref, *rest = refs
    if has_scale:
        ks_ref, vs_ref, *rest = rest
    o_ref, m_sc, l_sc, acc = rest
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    sl = sl_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    @pl.when(j * bs < sl)
    def _page():
        q = q_ref[0, 0]                               # (t, dk)
        k = k_ref[0, 0]                               # (bs, dk)
        v = v_ref[0, 0]
        if has_scale:
            k = k.astype(jnp.float32) * _row_scales(ks_ref, pack,
                                                    k.shape[-1])
            v = v.astype(jnp.float32) * _row_scales(vs_ref, pack,
                                                    v.shape[-1])
        heads = _packed_scores(q, k) if pack \
            else (_dot(q, k, trans_b=True),)           # (t, bs) fp32
        # per-row causal mask: row r attends positions <= sl - t + r
        shape = heads[0].shape
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        mask = pos <= sl - t + row
        corrs = []
        pas = []
        for hh, s in enumerate(heads):
            s = jnp.where(mask, s, _NEG)
            m_prev = m_sc[:, hh:hh + 1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1,
                                                keepdims=True))
            corr = jnp.exp2((m_prev - m_cur) * a)
            p = jnp.exp2((s - m_cur) * a)
            p = jnp.where(mask, p, 0.0)
            l_sc[:, hh:hh + 1] = l_sc[:, hh:hh + 1] * corr \
                + jnp.sum(p, axis=1, keepdims=True)
            m_sc[:, hh:hh + 1] = m_cur
            pas.append(p)
            corrs.append(corr)
        if pack:
            corr_w = _pack_lane_cols(corrs[0], corrs[1], acc.shape[1])
            acc[:] = acc[:] * corr_w + _packed_out(pas[0], pas[1], v)
        else:
            acc[:] = acc[:] * corrs[0] \
                + _dot(pas[0].astype(v.dtype), v)

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0, 0] = _normalized(l_sc, acc, pack).astype(o_ref.dtype)


def _decode_paged_multi(q4, k_cache, v_cache, block_tables, seq_lens,
                        scale, k_scale, v_scale, pack):
    """pallas_call driver for the t-row chunk path: grid
    (b, head groups, pages) like the single-token driver, q/o blocks
    carry the whole (t, dk) chunk per program."""
    b, hk, t, dk = q4.shape
    nb, _, bs, _ = k_cache.shape
    mp = block_tables.shape[1]
    a = float(scale) * _LOG2E
    has_scale = k_scale is not None
    g = 2 if pack else 1

    def qo_spec():
        return pl.BlockSpec((1, 1, t, dk),
                            lambda b_, h_, j, bt, sl: (b_, h_, 0, 0),
                            memory_space=pltpu.VMEM)

    kv_spec = pl.BlockSpec(
        (1, 1, bs, dk),
        lambda b_, h_, j, bt, sl: (bt[b_, j], h_, 0, 0),
        memory_space=pltpu.VMEM)
    in_specs = [qo_spec(), kv_spec, kv_spec]
    operands = [q4, k_cache, v_cache]
    if has_scale:
        sc_spec = pl.BlockSpec(
            (1, 1, g, bs),
            lambda b_, h_, j, bt, sl: (bt[b_, j], h_, 0, 0),
            memory_space=pltpu.VMEM)
        in_specs += [sc_spec, sc_spec]
        operands += [_scale_operand(k_scale, hk, g),
                     _scale_operand(v_scale, hk, g)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, mp),
        in_specs=in_specs,
        out_specs=qo_spec(),
        scratch_shapes=[
            pltpu.VMEM((t, 128), jnp.float32),
            pltpu.VMEM((t, 128), jnp.float32),
            pltpu.VMEM((t, dk), jnp.float32),
        ])
    return pl.pallas_call(
        functools.partial(_decode_multi_kernel, a, bs, t, pack,
                          has_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, t, dk), q4.dtype),
        name="paged_flash_decode_multi",
        interpret=_interpret(),
    )(block_tables, seq_lens, *operands)


def flash_decode_multi(q: jnp.ndarray, k_cache: jnp.ndarray,
                       v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                       seq_lens: jnp.ndarray, *,
                       scale: Optional[float] = None,
                       k_scale: Optional[jnp.ndarray] = None,
                       v_scale: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
    """Multi-token paged attention: ``t`` contiguous query tokens per
    sequence against the block-paged cache — the speculative-verify /
    chunked-prefill counterpart of :func:`flash_decode`.

    ``q`` is (b, t, h, d); row ``r`` of sequence ``b`` sits at global
    position ``seq_lens[b] - t + r`` (its k/v, like every earlier
    position's, must already be written to the cache — the serving
    step writes the whole chunk before attending, so each token sees
    itself and its in-chunk predecessors through the pages).  The
    causal rule is per row: attend to positions ``<= seq_lens[b] - t
    + r``.  Rows whose position is negative (front padding of a short
    chunk) and rows of an inactive sequence (``seq_lens == 0``) emit
    exactly 0.  Layout/packing/int8 conventions are identical to
    :func:`flash_decode`; at ``t == 1`` the two paths compute the
    same attention.  Inference-only (no VJP)."""
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    nb, hk, bs, dk = k_cache.shape
    if v_cache.shape != k_cache.shape:
        raise ValueError(f"k/v cache shapes differ: {k_cache.shape} "
                         f"vs {v_cache.shape}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if hk == h and dk == d:
        pack = False
    elif h % 2 == 0 and hk == h // 2 and dk == 2 * d:
        pack = True
    else:
        raise ValueError(
            f"cache head layout {(hk, dk)} matches neither unpacked "
            f"{(h, d)} nor head-packed {(h // 2, 2 * d)} for q "
            f"{q.shape}")
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if sc is not None and sc.shape != (nb, h, bs):
            raise ValueError(f"{name} shape {sc.shape} != expected "
                             f"{(nb, h, bs)} (global head order)")
    # (b, t, h, d) -> (b, hk, t, dk): the pack is a reshape on the
    # trailing axes (same free-at-decode property as the single-token
    # path), then heads move ahead of the chunk axis
    q4 = pack_decode_heads(q) if pack else q
    q4 = q4.transpose(0, 2, 1, 3)
    out = _decode_paged_multi(q4, k_cache, v_cache,
                              block_tables.astype(jnp.int32),
                              seq_lens.astype(jnp.int32), scale,
                              k_scale, v_scale, pack)
    out = out.transpose(0, 2, 1, 3)                    # (b, t, hk, dk)
    return unpack_decode_heads(out) if pack else out


# --- jnp twin ---------------------------------------------------------------

def dequantize_kv(cache: jnp.ndarray,
                  scale: Optional[jnp.ndarray]) -> jnp.ndarray:
    """int8 (nb, hk, bs, dk) cache + (nb, h, bs) per-row scales -> f32
    (handles the packed lane-half layout); float caches pass through."""
    if scale is None:
        return cache
    nb, hk, bs, dk = cache.shape
    h = scale.shape[1]
    if hk == h:
        s = scale[..., None]                           # (nb, h, bs, 1)
    else:
        # packed: lane half i of pair p is global head 2p+i
        s = scale.reshape(nb, hk, 2, bs).transpose(0, 1, 3, 2)
        s = jnp.repeat(s, dk // 2, axis=-1)            # (nb, hk, bs, dk)
    return cache.astype(jnp.float32) * s


def paged_attention_reference(q, k_cache, v_cache, block_tables,
                              seq_lens, scale=None, k_scale=None,
                              v_scale=None):
    """Dense jnp twin of :func:`flash_decode`: gather every row's pages
    into contiguous (b, h, pages*bs, d) k/v, mask by global position,
    fp32 softmax.  The parity oracle and the naive full-gather decode
    baseline the serving bench row compares the kernel against."""
    b, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    nb, hk, bs, dk = k_cache.shape
    k_cache = dequantize_kv(k_cache, k_scale)
    v_cache = dequantize_kv(v_cache, v_scale)
    if hk != h:   # packed storage -> per-head view
        k_cache = unpack_decode_heads(
            k_cache.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
        v_cache = unpack_decode_heads(
            v_cache.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    mp = block_tables.shape[1]
    # (b, mp, h, bs, d) -> (b, h, mp*bs, d)
    k = k_cache[block_tables].transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, mp * bs, d)
    v = v_cache[block_tables].transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, mp * bs, d)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(mp * bs, dtype=jnp.int32)[None, None, :]
    mask = pos < seq_lens[:, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)             # inactive rows
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhk,bhkd->bhd", p / safe, v.astype(jnp.float32))
    o = jnp.where(l == 0.0, 0.0, o)
    return o.astype(q.dtype)


def paged_attention_multi_reference(q, k_cache, v_cache, block_tables,
                                    seq_lens, scale=None, k_scale=None,
                                    v_scale=None):
    """Dense jnp twin of :func:`flash_decode_multi`: gather every
    row's pages, mask per query row by the contiguous-chunk causal
    rule (row ``r`` attends positions ``<= seq_lens[b] - t + r``),
    fp32 softmax.  The parity oracle for the multi-token kernel and
    the dense verify/chunk baseline (``decode_attention="reference"``)."""
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    nb, hk, bs, dk = k_cache.shape
    k_cache = dequantize_kv(k_cache, k_scale)
    v_cache = dequantize_kv(v_cache, v_scale)
    if hk != h:
        k_cache = unpack_decode_heads(
            k_cache.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
        v_cache = unpack_decode_heads(
            v_cache.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    mp = block_tables.shape[1]
    k = k_cache[block_tables].transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, mp * bs, d)
    v = v_cache[block_tables].transpose(0, 2, 1, 3, 4) \
        .reshape(b, h, mp * bs, d)
    s = jnp.einsum("bthd,bhkd->bthk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale    # (b, t, h, k)
    pos = jnp.arange(mp * bs, dtype=jnp.int32)[None, None, None, :]
    qpos = (seq_lens[:, None].astype(jnp.int32) - t
            + jnp.arange(t, dtype=jnp.int32)[None, :])   # (b, t)
    mask = pos <= qpos[:, :, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bthk,bhkd->bthd", p / safe,
                   v.astype(jnp.float32))
    o = jnp.where(l == 0.0, 0.0, o)
    return o.astype(q.dtype)
