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
(b, pages, bs, d) copy anywhere (the dense gather twin behind
``decode_attention="reference"`` does exactly that copy).

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

The grid is ``(batch row, head-group chunk, page)`` and a step carries
a **page's worth of work**: every head group of the row's page, one
contiguous ``(hk, bs, dk)`` block each of k and v (32 KB at the served
GPT-2 shape, where ``(1, 1, bs, dk)`` was 4 KB and the grid eight times
as long).  The chunk axis is 1 at every served decode shape; it exists
for steps whose working set would pass a stated VMEM budget
(:func:`_heads_per_step`: the largest divisor of ``hk`` that fits, read
from the cache's and the query chunk's shape alone) — a cache of very
many heads or very long blocks, and the multi-token path's long chunks
(below).  Inside a step the head groups'
independent softmax chains are the row blocks of ONE score tile: all
``hk`` queries against the page's ``hk * bs`` k rows in one full-width
matmul, the products of a query with another group's rows masked away
(:func:`_own_page_mask`) — at 8 groups x 16 slots the tile is a single
fp32 vreg and a page costs two matmuls a product where per-head
programs ran ``hk`` slivers.  The wasted MXU columns are free; grid
steps, and the fixed ~6 us every (row, head group) pass of the old
grid paid, were not (PERF.md, PR 28).  One page a step: several (a
BlockSpec a page) cut a live page's cost threefold in the kernel
alone, but a cache passed as several operands loses XLA's fast-memory
placement of the per-layer cache slices, which costs more than the
kernel gains while those slices exist (PERF.md, PR 28).

Online softmax runs across pages exactly as the training forward runs
across k-blocks: per-(batch row) scratch carries m/l/acc — one row a
head group — over the page grid dimension, pages wholly past
``seq_len`` are skipped via ``pl.when`` (their table entry is the dump
page, so no fetch either: a skipped step costs the bare grid step),
and the straddling page masks by global position.  Softmax math is
fp32 with the exp2 pre-folded constants.  A row's arithmetic depends
on that row's pages alone, never on the batch or page rung.

The multi-token path (:func:`flash_decode_multi`: speculative verify,
chunked extend) is the same program with ``t`` query rows a head group.
Its carries, q and o blocks and score tiles grow with ``hg * t``, so
the same budget takes the head groups apart as the chunk grows: at the
served cache every group a step up to a 128-token chunk, four at 256,
one at the 1,024-token rung (where the tile is the per-head program's
own ``(t, bs)``, and nothing of another head is computed).

Grouped-query attention and a causal window (serving's ``rope_moe``
family) are the same program again.  An unpacked cache of fewer heads
than ``q`` has: the ``h // hk`` query heads that read a cache head ride
as that head group's query rows (``groups * t`` of them), so a page is
still fetched once and scored in one matmul.  ``window``: each row
keeps the newest ``window`` positions; the page axis of the grid is
then as long as the pages a window can straddle (33 of 16 for 512), not
as the page rung, and starts at the row's first page in the window, so
the pages behind it cost no fetch, no compute and no grid step (a
skipped step that still has its index map to evaluate took 0.16 us on a
v5e, 544 of them a row more than the 33 live pages; PERF.md, PR 29).

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

__all__ = ["flash_decode", "flash_decode_multi", "eva_flash_decode",
           "paged_attention_reference",
           "paged_attention_multi_reference", "eva_attention_reference",
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


# What one grid step's working set may take of VMEM: well inside the
# 16 MiB scoped default, so the compiler's own temporaries have room
# (tests/test_tpu_compile.py holds the estimate to Mosaic's verdict:
# the cells' cache with a 1,024-token chunk at every head a step was
# refused at 37.2 MiB, where this count reads 38.2).
_STEP_VMEM_BYTES = 6 * 1024 * 1024


def _step_vmem_bytes(hg: int, t: int, bs: int, dk: int) -> int:
    """The working set of a step that carries ``hg`` head groups of
    ``t`` query rows: k and v pages double-buffered (two bytes an
    element at most) and up to four page-sized fp32 temporaries (the
    int8 path dequantizes, the packed path rotates); per query row the
    m/l/acc carries, q and o double-buffered, and up to four fp32
    temporaries of the score tile's width (a lane tile at least)."""
    page, rows = hg * bs * dk, hg * t
    return (page * (2 * 2 * 2 + 4 * 4)
            + rows * ((2 * 128 + dk) * 4 + 2 * 2 * dk * 2
                      + 4 * max(hg * bs, 128) * 4))


def _heads_per_step(hk: int, t: int, bs: int, dk: int) -> int:
    """Head groups one grid step carries: every one of ``hk`` unless
    that step's working set would pass :data:`_STEP_VMEM_BYTES`, then
    the largest divisor of ``hk`` that does not — one head group a step
    at a long chunk, where the carries alone are megabytes.  A divisor
    short of ``hk`` must make ``hg * t`` a multiple of 8: the q block is
    ``(hg * t, dk)`` of a flat ``(hk * t, dk)`` and Mosaic takes a
    second-minor block dimension only whole or in sublane tiles; where
    no such divisor fits, the smallest is taken and the compiler has the
    last word.  Read from the cache's and the chunk's shape alone —
    never from the batch or page rung, so a row's arithmetic is the same
    under every bucket."""
    ok = [g for g in range(1, hk + 1)
          if hk % g == 0 and (g == hk or g * t % 8 == 0)]
    fits = [g for g in ok
            if _step_vmem_bytes(g, t, bs, dk) <= _STEP_VMEM_BYTES]
    return max(fits) if fits else min(ok)


def _own_page_mask(shape, t, bs, page0, sl, groups=1, window=None):
    """The (hg*groups*t, hg*bs) score tile's live entries.  A head
    group's rows are its ``groups`` query heads (grouped-query
    attention: several query heads read one cache head), ``t`` queries
    each: row ``r`` is query ``r % t`` of query head ``r // t``, whose
    cache head is ``r // (groups * t)``; column ``c`` is slot ``c % bs``
    of head group ``c // bs``'s page.  Live = the query's own head
    group (the off-diagonal blocks are other heads' products, computed
    because one full-width matmul is cheaper than ``hg`` slivers, and
    never used) AND the causal rule of a contiguous chunk ending at
    ``sl``: position <= sl - t + query -- at ``t == 1``, ``pos < sl``
    -- AND, under a ``window``, position > that bound - window (the
    window counts the query's own position)."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    rows = groups * t
    if rows == 1:
        head, query = row, 0
    else:
        head = jax.lax.div(row, rows)
        if t == 1:
            query = 0
        elif groups == 1:
            query = row - head * t
        else:
            query = row - jax.lax.div(row, t) * t
    slot = col - head * bs        # in [0, bs): the row's own head group
    live = (slot >= 0) & (slot < bs) & (page0 + slot <= sl - t + query)
    if window is not None:
        live = live & (page0 + slot > sl - t + query - window)
    return live


def _row_scales(s_ref, hg, pack, width):
    """This page's per-row dequant factors for the (hg*bs, width) k/v
    tile: a (hg*bs, 1) column — or, packed, a (hg*bs, width) array with
    each head's factor on its lane half.  ``s_ref`` is the
    (1, hg, g, bs) block of the (nb, hk, g, bs) scale view."""
    def col(gg):
        return jnp.concatenate(
            [s_ref[0, hh, gg, :][:, None] for hh in range(hg)], axis=0)
    if pack:
        return _pack_lane_cols(col(0), col(1), width)
    return col(0)


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
    step's head groups become whole trailing (g, bs) blocks, which the
    TPU lowering accepts where a size-g block on an axis of h is
    refused."""
    nb, _, bs = scale.shape
    return scale.reshape(nb, hk, g, bs)


def _paged_kernel(a, bs, t, hg, pack, has_scale, groups, window, *refs):
    """One (batch row, head-group chunk, page) program: ALL ``hg`` head
    groups of the row's page, ``groups`` query heads of ``t`` query rows
    each (``t == 1``: plain decode; ``groups == 1``: as many query as
    cache heads).  Scalar-prefetch refs lead: block tables (consumed by the
    index maps, unused here) and seq_lens.  The ``hg`` independent
    online-softmax chains are the row blocks of one (hg*t, hg*bs)
    score tile — at the serving shape (8 groups x 16 slots) exactly one
    fp32 vreg — so a page costs two full-width matmuls a product
    instead of ``hg`` slivers; see :func:`_own_page_mask`.  Scratch m/l
    ride columns 0..g-1 of a (hg*t, 128) carry — the training kernels'
    column-per-head idiom."""
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

    # a page wholly past the sequence contributes nothing — skip it.
    # Its block-table entry is the dump page, as is its neighbours', so
    # the pipeline re-fetches nothing either: what the bucketed page
    # rung costs a short row is bare grid steps (~10 ns each on v5e).
    # Under a window the grid is only as long as a window's pages and
    # starts at the first page the chunk's oldest query (position
    # sl - t) still sees: the pages behind it are never visited.
    if window is None:
        page = j
    else:
        page = jnp.maximum(sl - t - window + 1, 0) // bs + j
    live = page * bs < sl

    @pl.when(live)
    def _page():
        dk = acc.shape[1]
        q = q_ref[0]                                  # (hg*t, dk)
        k = k_ref[0].reshape(hg * bs, dk)             # rows (head, slot)
        v = v_ref[0].reshape(hg * bs, dk)
        if has_scale:
            # int8 rows -> f32 in VMEM; per-row scales so history is
            # never requantized by an append.  Packed: each lane half
            # is one head's row, scaled by that head's factor.
            k = k.astype(jnp.float32) * _row_scales(ks_ref, hg, pack, dk)
            v = v.astype(jnp.float32) * _row_scales(vs_ref, hg, pack, dk)
        heads = _packed_scores(q, k) if pack \
            else (_dot(q, k, trans_b=True),)           # (hg*t, hg*bs) fp32
        mask = _own_page_mask(heads[0].shape, t, bs, page * bs, sl,
                              groups, window)
        pas, corrs = [], []
        for hh, s in enumerate(heads):
            s = jnp.where(mask, s, _NEG)
            m_prev = m_sc[:, hh:hh + 1]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1,
                                                keepdims=True))
            corr = jnp.exp2((m_prev - m_cur) * a)
            p = jnp.exp2((s - m_cur) * a)
            # masked entries: (s - m_cur) = 0 there when every column
            # so far is masked — zero p explicitly so dead rows sum to
            # l = 0 and emit exactly 0, and so another head group's
            # v rows drop out of the p v product below
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
        out = _normalized(l_sc, acc, pack).astype(o_ref.dtype)
        for hh in range(hg * groups):              # query heads
            o_ref[0, hh] = out[hh * t:(hh + 1) * t]


def _paged_program(q4, k_cache, v_cache, block_tables, seq_lens, scale,
                   k_scale, v_scale, pack, window, interpret):
    """What both pallas_call drivers share: ``(kernel, keywords,
    operands)`` for q4 = (b, h, t, dk), ``h`` the (packed) query heads:
    ``groups = h // hk`` of them read each of the cache's ``hk`` heads
    and ride as ``groups * t`` query rows of that head group.  Grid
    (b, hk // hg, pages) with ``hg`` = :func:`_heads_per_step` (all
    ``hk`` at every served decode shape, so the middle axis is 1);
    block tables + seq_lens are scalar-prefetched so the k/v index maps
    read the page id directly -- the gather IS the pipeline's block
    fetch, one contiguous (hg, bs, dk) page a step.  Under a ``window``
    the page axis is as long as the pages a window can straddle, not as
    the page rung, and step ``j`` is the ``j``-th page from the first
    one in the row's window: pages behind the window are neither
    fetched nor computed nor paid a grid step for."""
    b, h, t, dk = q4.shape
    nb, hk, bs, _ = k_cache.shape
    groups = h // hk
    rows = groups * t
    mp = block_tables.shape[1]
    a = float(scale) * _LOG2E
    has_scale = k_scale is not None
    g = 2 if pack else 1
    hg = _heads_per_step(hk, rows, bs, dk)

    def row_map(b_, h_, j, bt, sl):
        return (b_, h_, 0, 0)

    if window is None:
        steps = mp

        def page_map(b_, h_, j, bt, sl):
            return (bt[b_, j], h_, 0, 0)
    else:
        # window + t - 1 positions straddle at most this many pages
        steps = min(mp, (window + t - 2) // bs + 2)

        def page_map(b_, h_, j, bt, sl):
            first = jnp.maximum(sl[b_] - t - window + 1, 0) // bs
            return (bt[b_, jnp.minimum(first + j, mp - 1)], h_, 0, 0)

    # q rides flat, (b, h*t, dk), so a step's queries are one
    # (hg*rows, dk) tile with no in-kernel relayout; o keeps the 4-D
    # (b, h, t, dk) -- a block's trailing (t, dk) is the array's own
    kv_spec = pl.BlockSpec((1, hg, bs, dk), page_map,
                           memory_space=pltpu.VMEM)
    in_specs = [pl.BlockSpec((1, hg * rows, dk),
                             lambda b_, h_, j, bt, sl: (b_, h_, 0),
                             memory_space=pltpu.VMEM),
                kv_spec, kv_spec]
    operands = [q4.reshape(b, h * t, dk), k_cache, v_cache]
    if has_scale:
        sc_spec = pl.BlockSpec((1, hg, g, bs), page_map,
                               memory_space=pltpu.VMEM)
        in_specs += [sc_spec, sc_spec]
        operands += [_scale_operand(k_scale, hk, g),
                     _scale_operand(v_scale, hk, g)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk // hg, steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hg * groups, t, dk), row_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((hg * rows, 128), jnp.float32),
            pltpu.VMEM((hg * rows, 128), jnp.float32),
            pltpu.VMEM((hg * rows, dk), jnp.float32),
        ])
    kernel = functools.partial(_paged_kernel, a, bs, t, hg, pack,
                               has_scale, groups, window)
    keywords = dict(
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, dk), q4.dtype),
        interpret=interpret)
    return kernel, keywords, (block_tables, seq_lens, *operands)


# Both drivers are jitted: a decode program calls its driver once a
# layer with the same shapes, and under the outer jit an inner one is
# traced and lowered once per program, not once per layer.  That
# Pallas -> Mosaic lowering is Python time on every start, compile
# cache hit or not (PERF.md, PR 28: 24 layers x 8 decode buckets).
# ``interpret`` is an argument so that it keys the trace.
_DRIVER_STATICS = ("scale", "pack", "window", "interpret")
_jit_driver = functools.partial(jax.jit, static_argnames=_DRIVER_STATICS)


@functools.partial(jax.jit, static_argnames=_DRIVER_STATICS + ("name",))
def _decode_paged(q3, k_cache, v_cache, block_tables, seq_lens, scale,
                  k_scale, v_scale, pack, window, interpret,
                  name="paged_flash_decode"):
    """The single-token pallas_call driver: (b, h, dk) queries are the
    ``t == 1`` chunk.  The call's output stays ``(b, h, 1, dk)``, ``h``
    the query heads, and its first operand the block table as the
    engine built it."""
    kernel, keywords, operands = _paged_program(
        q3[:, :, None, :], k_cache, v_cache, block_tables, seq_lens,
        scale, k_scale, v_scale, pack, window, interpret)
    return pl.pallas_call(kernel, name=name,
                          **keywords)(*operands)[:, :, 0, :]


def _cache_is_packed(q_shape, k_cache, v_cache, k_scale, v_scale):
    """Validate the cache (and scales) against q's trailing (h, d) and
    say whether it is stored head-packed -- the cache layout decides the
    kernel path.  Unpacked, the cache may hold fewer heads than q has:
    ``h // hk`` query heads then read each cache head (query head ``j``
    reads cache head ``j // (h // hk)``)."""
    h, d = q_shape[-2:]
    nb, hk, bs, dk = k_cache.shape
    if v_cache.shape != k_cache.shape:
        raise ValueError(f"k/v cache shapes differ: {k_cache.shape} "
                         f"vs {v_cache.shape}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if dk == d and h % hk == 0:
        pack = False
    elif h % 2 == 0 and hk == h // 2 and dk == 2 * d:
        pack = True
    else:
        raise ValueError(
            f"cache head layout {(hk, dk)} matches neither unpacked "
            f"{(h, d)} (or a divisor of its heads) nor head-packed "
            f"{(h // 2, 2 * d)} for q {q_shape}")
    kv_heads = 2 * hk if pack else hk
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if sc is not None and sc.shape != (nb, kv_heads, bs):
            raise ValueError(f"{name} shape {sc.shape} != expected "
                             f"{(nb, kv_heads, bs)} (global head order)")
    return pack


def flash_decode(q: jnp.ndarray, k_cache: jnp.ndarray,
                 v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                 seq_lens: jnp.ndarray, *,
                 scale: Optional[float] = None,
                 k_scale: Optional[jnp.ndarray] = None,
                 v_scale: Optional[jnp.ndarray] = None,
                 window: Optional[int] = None) -> jnp.ndarray:
    """Single-query attention over a block-paged KV cache.

    ``q`` is (b, h, d) — one query token per sequence; the cache is
    (nb, hk, bs, dk) block-major (see the module note for the packed
    ``hk``/``dk`` convention — the cache layout decides the kernel
    path, so the pool that allocated it is the single source of
    truth).  ``block_tables`` (b, max_pages) int32 names each row's
    pages; ``seq_lens`` (b,) bounds the attended positions, 0 marking
    an inactive row (output exactly 0).  ``k_scale``/``v_scale``
    (nb, h, bs) fp32 arm the int8 weight-only dequant path.  An
    unpacked cache of fewer heads than ``q`` is grouped-query
    attention: query head ``j`` reads cache head ``j // (h // hk)``.
    ``window`` (static) keeps the newest ``window`` positions of each
    row, the query's own included; pages wholly behind it are neither
    fetched nor computed.  Returns (b, h, d) in q's dtype.
    Inference-only (no VJP).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pack = _cache_is_packed(q.shape, k_cache, v_cache, k_scale, v_scale)
    q3 = pack_decode_heads(q) if pack else q
    out = _decode_paged(q3, k_cache, v_cache,
                        block_tables.astype(jnp.int32),
                        seq_lens.astype(jnp.int32), scale,
                        k_scale, v_scale, pack, window, _interpret())
    return unpack_decode_heads(out) if pack else out


def eva_flash_decode(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                     summary_lens: jnp.ndarray, window_lens: jnp.ndarray,
                     *, scale: Optional[float] = None) -> jnp.ndarray:
    """EVA's decode attention: ONE softmax of each row's query over two
    paged segments, each with its own length -- ``summary_lens[b]``
    pooled (key, value) rows, one for every chunk of the windows before
    the query's, and the ``window_lens[b]`` exact rows of its own window
    up to itself.

    Both segments live in the same cache arrays (a pooled row has a
    cached row's shape) and ``block_tables[b]`` names them in one run:
    the summary pages first, then the window's.  A closed window's
    pooled rows fill whole pages (:class:`~apex_tpu.serving.kv_cache.
    KVCacheConfig` holds ``window`` to a multiple of ``block_size^2``),
    so **``summary_lens`` is a multiple of the page size**, the window's
    first row follows the last summary row with no gap, and the union is
    the leading ``summary_lens + window_lens`` rows of the table: the
    paged program of :func:`flash_decode` reads exactly those, a page a
    grid step, and pays a bare grid step for the rest of the page rung.
    Rows whose lengths are both 0 are inactive and emit exactly 0.
    Shapes and layouts as :func:`flash_decode` (float caches; the call's
    output ``(b, h, 1, d)`` with the block table its first operand), its
    name in the HLO ``eva_flash_decode``.  Inference-only."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pack = _cache_is_packed(q.shape, k_cache, v_cache, None, None)
    q3 = pack_decode_heads(q) if pack else q
    rows = summary_lens.astype(jnp.int32) + window_lens.astype(jnp.int32)
    out = _decode_paged(q3, k_cache, v_cache,
                        block_tables.astype(jnp.int32), rows, scale,
                        None, None, pack, None, _interpret(),
                        name="eva_flash_decode")
    return unpack_decode_heads(out) if pack else out


# --- multi-token path (speculative verify / chunked prefill) ---------------

@_jit_driver
def _decode_paged_multi(q4, k_cache, v_cache, block_tables, seq_lens,
                        scale, k_scale, v_scale, pack, window, interpret):
    """pallas_call driver of the t-row chunk path: (b, h, t, dk)
    queries through the single-token driver's program."""
    kernel, keywords, operands = _paged_program(
        q4, k_cache, v_cache, block_tables, seq_lens, scale, k_scale,
        v_scale, pack, window, interpret)
    return pl.pallas_call(kernel, name="paged_flash_decode_multi",
                          **keywords)(*operands)


def flash_decode_multi(q: jnp.ndarray, k_cache: jnp.ndarray,
                       v_cache: jnp.ndarray, block_tables: jnp.ndarray,
                       seq_lens: jnp.ndarray, *,
                       scale: Optional[float] = None,
                       k_scale: Optional[jnp.ndarray] = None,
                       v_scale: Optional[jnp.ndarray] = None,
                       window: Optional[int] = None) -> jnp.ndarray:
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
    exactly 0.  Layout/packing/int8 conventions, grouped query heads
    and ``window`` (each row keeps the ``window`` positions ending at
    its own) are identical to :func:`flash_decode`; at ``t == 1`` the
    two paths compute the same attention.  Inference-only (no VJP)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pack = _cache_is_packed(q.shape, k_cache, v_cache, k_scale, v_scale)
    # (b, t, h, d) -> (b, hk, t, dk): the pack is a reshape on the
    # trailing axes (same free-at-decode property as the single-token
    # path), then heads move ahead of the chunk axis
    q4 = pack_decode_heads(q) if pack else q
    q4 = q4.transpose(0, 2, 1, 3)
    out = _decode_paged_multi(q4, k_cache, v_cache,
                              block_tables.astype(jnp.int32),
                              seq_lens.astype(jnp.int32), scale,
                              k_scale, v_scale, pack, window,
                              _interpret())
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


def _per_head_cache(cache, scale, h, d):
    """(nb, h, bs, d) float view of a stored cache for ``h`` query
    heads of size ``d``: dequantized, head pairs unpacked, and each
    cache head repeated for the query heads that read it."""
    cache = dequantize_kv(cache, scale)
    if cache.shape[-1] != d:   # packed storage -> per-head view
        cache = unpack_decode_heads(
            cache.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    return jnp.repeat(cache, h // cache.shape[1], axis=1) \
        if cache.shape[1] != h else cache


def paged_attention_reference(q, k_cache, v_cache, block_tables,
                              seq_lens, scale=None, k_scale=None,
                              v_scale=None, window=None):
    """Dense jnp twin of :func:`flash_decode`: gather every row's pages
    into contiguous (b, h, pages*bs, d) k/v, mask by global position
    (and by the ``window``), fp32 softmax.  The parity oracle and the
    naive full-gather decode baseline the kernel is held against
    (chip_smoke.py, the parity tests)."""
    b, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    bs = k_cache.shape[2]
    k_cache = _per_head_cache(k_cache, k_scale, h, d)
    v_cache = _per_head_cache(v_cache, v_scale, h, d)
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
    if window is not None:
        mask = mask & (pos >= seq_lens[:, None, None] - window)
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


def eva_attention_reference(q, k_cache, v_cache, block_tables,
                            summary_lens, window_lens, scale=None):
    """Dense jnp twin of :func:`eva_flash_decode`: the table's pages
    gathered whole, one float32 softmax over its leading ``summary_lens
    + window_lens`` rows (the summary pages are whole, so the window's
    first row follows the last pooled row)."""
    return paged_attention_reference(
        q, k_cache, v_cache, block_tables,
        summary_lens.astype(jnp.int32) + window_lens.astype(jnp.int32),
        scale=scale)


def paged_attention_multi_reference(q, k_cache, v_cache, block_tables,
                                    seq_lens, scale=None, k_scale=None,
                                    v_scale=None, window=None):
    """Dense jnp twin of :func:`flash_decode_multi`: gather every
    row's pages, mask per query row by the contiguous-chunk causal
    rule (row ``r`` attends positions ``<= seq_lens[b] - t + r``, and
    under a ``window`` only the newest ``window`` of them), fp32
    softmax.  The parity oracle for the multi-token kernel and the
    dense verify/chunk baseline (``decode_attention="reference"``)."""
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    bs = k_cache.shape[2]
    k_cache = _per_head_cache(k_cache, k_scale, h, d)
    v_cache = _per_head_cache(v_cache, v_scale, h, d)
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
    if window is not None:
        mask = mask & (pos > qpos[:, :, None, None] - window)
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
