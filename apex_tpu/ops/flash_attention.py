"""Pallas flash attention (forward + backward), bf16-first.

TPU-native successor to the reference's fused attention kernels: FMHA
(ref: apex/contrib/csrc/fmha — sm80, seqlen <= 512, head dim 64) and the
fast_multihead_attn family (ref: apex/contrib/csrc/multihead_attn).
Blockwise online-softmax attention removes both the O(s^2)
materialization (the reference's core attention materializes
[b, np, sq, sk], ref: apex/transformer/testing/standalone_gpt.py) and
the shape caps: any sq/sk (padded to block multiples), head dim 64-256,
causal or full attention.

Layout: q (b, h, sq, d), k/v (b, h, sk, d).  Matmuls hit the MXU in the
input dtype with fp32 accumulation; softmax math is fp32.  At d=64 with
even h the per-tensor drivers pack head PAIRS onto one 128-lane tile
and run every matmul full-width via a sign rotation — see the
head-packing note above ``set_head_packing`` (escape hatch:
``APEX_TPU_FLASH_PACK_D64=0``).

Kernel-economy notes (v5e profile at GPT-345M shapes, b=8 h=16 s=1024
d=64; structural matmul minimum fwd 262 us / bwd 611 us per call):
- ``exp2`` with pre-folded constants: softmax runs as
  ``exp2(s*a - m*a)`` with ``a = scale*log2(e)``, so no separate
  ``s*scale`` pass over the (bq, bk) score array and no ln<->log2
  conversion inside the hot loop.
- scale folding: the backward feeds ``v*scale`` to the ``dp`` matmul
  and pre-scales ``delta`` outside the kernel, turning
  ``ds = p*(dp-delta)*scale`` into ``ds = p*(dp'-delta')`` — one fewer
  score-shaped multiply.
- no materialized transposes: ``dv = p^T do`` / ``dk = ds^T q`` use
  ``dot_general`` contracting dim 0 of both operands (MXU-native)
  instead of ``.T``-then-matmul, which lowers to cross-lane VPU
  shuffles over the full score block.
- static mask elision: block-aligned sequences (the common case) skip
  the ``k_pos < sk`` compare entirely; q-padded rows are killed by
  padding the saved logsumexp with +BIG (``exp2 -> 0``) rather than by
  per-element masks.  Only ``causal`` and ``kv_mask`` pay a select.

Backward: when the padded sequence fits one block and d <= 64 (the
common case at the default 1024 blocks — e.g. GPT-345M s=1024), a
single fused kernel produces dq/dk/dv in one pass (5 matmuls; scores
and dp computed once).  Otherwise the standard two-kernel flash
backward runs: a dq pass (grid over q-blocks, accumulate over k) and a
dk/dv pass (grid over k-blocks, accumulate over q), both recomputing
probabilities from the saved per-row logsumexp.
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_BIG = 1e30
_LOG2E = math.log2(math.e)
# Tuned on v5e via the GPT-345M train-step profile (b=8, h=16, s=1024,
# d=64; device-time deltas are stable run-to-run even when wall clock is
# not), on the grid-blocked (b, h, s, d) drivers below, before the E
# layout existed: (1024, 1024) beats (512, 1024) — 56.4 vs 62.2 ms/step
# of kernel time across fwd+bwd — and (512, 512) loses despite its finer
# causal block skipping; wide lanes win on the MXU, and on a grid a
# finer tile pays grid steps and online-softmax rescales.  Inside one
# VMEM block it pays neither: the one-block E-layout kernels (what
# training runs) chunk the causal triangle by rows instead, see
# ``_e_chunk``.  VMEM at (1024, 1024),
# d<=256: q/k/v/acc blocks + fp32 scores ~7 MB, within the 16 MB
# budget (at d > 64 block_q is halved — see _clamp_blocks).  Env
# overrides (read at import) for bench-driven re-tuning.
from ..analysis.flags import flag_bool, flag_int

DEFAULT_BLOCK_Q = flag_int("APEX_TPU_FLASH_BLOCK_Q")
DEFAULT_BLOCK_K = flag_int("APEX_TPU_FLASH_BLOCK_K")

# --- d=64 head packing ------------------------------------------------------
#
# A d=64 head fills only HALF the 128-wide MXU lane tile: q k^T contracts
# 64 of 128 lanes and p v emits 64 of 128 output lanes, so the unpacked
# kernels cap near half the d=128 rate (docs/ROUND6_NOTES.md, round 5:
# 52.6/52.8 TF/s device at s=8192/16384 vs 97.3-98.2 at d=128) — at the
# reference FMHA's ONLY supported head dim (ref: setup.py:408-424).
#
# Fix: when d == 64 and h is even, the (b, h, s, d) drivers pack adjacent
# head pairs into one 128-lane tile, (b, h, s, 64) -> (b, h/2, s, 128),
# and every per-head matmul pair is recovered from two FULL-WIDTH
# matmuls via a sign rotation.  With sigma = [+1]*64 ++ [-1]*64 on the
# packed lane axis and packed operands X = [X0|X1], W = [W0|W1]:
#
#   X W^T         = X0 W0^T + X1 W1^T        (contraction: all 128 lanes)
#   X (W*sigma)^T = X0 W0^T - X1 W1^T
#
# so S0/S1 fall out of a half-sum/half-difference instead of two
# half-width d=64 contractions; the mirrored combine
# ((A0+A1) W + (A0-A1) (W*sigma)) / 2 = [A0 W0 | A1 W1] does the same
# for the products whose OUTPUT axis is the packed lane axis (p v, ds k,
# and the dim-0-contracting dk/dv forms).  Cross-head terms cancel in
# the rotation algebra — no block-diagonal masking pass exists anywhere.
# Per k-block a packed program runs 2 matmuls per score-side product for
# BOTH heads where the unpacked kernel ran 2 half-width ones PER head:
# ~2x useful MXU throughput.  Softmax, causal/segment masking, the
# dropout coordinate hash (per GLOBAL head) and the lse/delta sidebands
# stay per-head, so the packed path is numerically the same computation
# up to fp reassociation in the rotation.  One rounding caveat beyond
# pure reassociation: in the low-precision combines the SUM/DIFFERENCE
# of the pair's score-shaped arrays is what gets rounded to the input
# dtype, so each head's products carry absolute error ~ulp of the
# PAIR's combined magnitude — in bf16, a head whose ds/p run orders of
# magnitude below its partner's absorbs noise at the partner's ulp
# scale (the unpacked path rounds each head alone).  Harmless at
# training tolerances; flip the escape hatch if a workload needs
# per-head-exact bf16 rounding.
#
# Escape hatch: APEX_TPU_FLASH_PACK_D64=0 (read at import) or
# set_head_packing(False) forces the old half-width path.  Packing is an
# implementation detail with no semantic contract — even a packed-fwd /
# unpacked-bwd mix is exact, because the backward recomputes p from the
# per-head lse and the dropout mask is coordinate-hashed, never
# tiling-derived.
_PACK_D64 = {"enabled": flag_bool("APEX_TPU_FLASH_PACK_D64")}


def set_head_packing(enabled: bool) -> None:
    """Toggle the d=64 head-pair packing (see the module note above).
    Flip OUTSIDE jit traces: a cached trace keeps whatever layout it was
    traced with (the results agree either way)."""
    _PACK_D64["enabled"] = bool(enabled)


def head_packing_enabled() -> bool:
    return _PACK_D64["enabled"]


def _use_head_packing(h: int, d: int) -> bool:
    return d == 64 and h % 2 == 0 and _PACK_D64["enabled"]


def _pack_head_pairs(x):
    """(b, h, s, d) -> (b, h/2, s, 2d): head 2j in lanes [0, d), head
    2j+1 in lanes [d, 2d) of pair j."""
    b, h, s, d = x.shape
    return x.reshape(b, h // 2, 2, s, d).transpose(0, 1, 3, 2, 4) \
        .reshape(b, h // 2, s, 2 * d)


def _unpack_head_pairs(x):
    """Inverse of :func:`_pack_head_pairs`."""
    b, hp, s, d2 = x.shape
    return x.reshape(b, hp, s, 2, d2 // 2).transpose(0, 1, 3, 2, 4) \
        .reshape(b, 2 * hp, s, d2 // 2)


def _lane_sign(dtype, width):
    """sigma row of the packing rotation: +1 on the first lane half,
    -1 on the second."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return jnp.where(lane < width // 2, 1.0, -1.0).astype(dtype)


def _packed_scores(x, w):
    """Both heads' (m, n) score-shaped products from lane-packed
    x = [X0|X1], w = [W0|W1]: returns (X0 W0^T, X1 W1^T) via the sum
    and sigma-rotated difference — two matmuls whose contraction spans
    all 128 lanes.  Serves q k^T and the backward's do (v*scale)^T."""
    sig = _lane_sign(w.dtype, w.shape[-1])
    ssum = _dot(x, w, trans_b=True)
    sdif = _dot(x, w * sig, trans_b=True)
    return 0.5 * (ssum + sdif), 0.5 * (ssum - sdif)


def _packed_out(a0, a1, w):
    """[A0 W0 | A1 W1] from per-head score-shaped A and lane-packed
    w = [W0|W1] — the mirrored combine keeps the OUTPUT lane axis
    full-width.  Serves p v (forward acc) and ds k (dq)."""
    sig = _lane_sign(w.dtype, w.shape[-1])
    asum = (a0 + a1).astype(w.dtype)
    adif = (a0 - a1).astype(w.dtype)
    return 0.5 * (_dot(asum, w) + _dot(adif, w * sig))


def _packed_out_t0(a0, a1, w):
    """[A0^T W0 | A1^T W1] — the dim-0-contracting (dk/dv) form of
    :func:`_packed_out`."""
    sig = _lane_sign(w.dtype, w.shape[-1])
    asum = (a0 + a1).astype(w.dtype)
    adif = (a0 - a1).astype(w.dtype)
    return 0.5 * (_dot_t0(asum, w) + _dot_t0(adif, w * sig))


def _pack_lane_cols(c0, c1, width):
    """Per-head (rows, 1) columns -> a (rows, width) lane-selected
    array: head 0's value on the first lane half, head 1's on the
    second (the packed accumulator's corr / 1/l multiplier)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return jnp.where(lane < width // 2, c0, c1)


def _clamp_blocks(block_q: int, block_k: int, d: int):
    """VMEM guard: the dk/dv backward holds four fp32 score-shaped
    temporaries (bq, bk) plus blocks and accumulators scaling with d.
    At d=64 (1024, 1024) fits comfortably; beyond that halve block_q so
    the worst case (d=256) stays ~11 MB of the 16 MB budget."""
    if d > 64:
        block_q = min(block_q, 512)
    return block_q, block_k


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, trans_b=False):
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _dot_t0(a, b):
    """a^T @ b via dot_general contracting dim 0 of both operands —
    the MXU consumes the transposed layout natively; an explicit
    ``a.T`` would materialize the block through VPU lane shuffles."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tri_mask(shape, q_off, k_off):
    """q_pos >= k_pos causal mask from thin iotas (broadcast compare:
    no full-block int32 position arrays)."""
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
    k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    return q_pos >= k_pos


def _window_mask(shape, q_off, k_off, window):
    """q_pos - k_pos < window: with the causal mask, each query keeps
    the ``window`` keys ending at its own position."""
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
    k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    return q_pos - k_pos < window


def _kcol_mask(shape, k_off, sk):
    k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (1, shape[1]), 1)
    return jnp.broadcast_to(k_pos < sk, shape)


def _u32of(x):
    """Non-negative int -> uint32 view (mask to 31 bits first: Mosaic
    has no checked int32->uint32 cast; see the seed contract note in
    :func:`flash_attention_e`)."""
    return jnp.bitwise_and(jnp.asarray(x, jnp.int32),
                           jnp.int32(0x7FFFFFFF)).astype(jnp.uint32)


def _keep_from_x(x, rate):
    """fmix32 + top-24-bit uniform -> keep mask (prob. 1 - rate)."""
    u32 = functools.partial(jnp.asarray, dtype=jnp.uint32)
    x = (x ^ (x >> 16)) * u32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * u32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # bitcast to int32 before the float convert — Mosaic has no
    # uint32->f32 cast, and after >> 8 the sign bit is 0
    f = jax.lax.bitcast_convert_type(x >> 8, jnp.int32) \
        .astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    return f >= jnp.float32(rate)


def _rand_keep_coords(shape, seed, salt_b, salt_head, row0, col0, rate):
    """Tiling-INDEPENDENT dropout keep mask: a pure function of
    (seed, batch, global head, GLOBAL row, GLOBAL col), so any block
    decomposition of the score matrix regenerates identical bits.  The
    sequence-parallel paths need exactly this: ring shards evaluate
    disjoint (row, col) windows of one global score matrix across
    differently-tiled fwd/bwd kernels, and the union must equal the
    mask a dense evaluation would draw (Liu et al. ring attention +
    the reference's in-kernel philox role, ref:
    apex/contrib/csrc/multihead_attn/dropout.h).

    ``row0``/``col0`` place ``shape`` in global coordinates (traced
    OK).  Global cols must stay below the 0x01000193 row stride for
    per-element uniqueness — 16.7M, far past any sequence here."""
    u32 = functools.partial(jnp.asarray, dtype=jnp.uint32)
    salt = (_u32of(seed) * u32(0x85EBCA6B)
            ^ _u32of(salt_b) * u32(0xC2B2AE35)
            ^ _u32of(salt_head) * u32(0x27D4EB2F))
    r = _u32of(row0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = _u32of(col0) + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    return _keep_from_x(r * u32(0x01000193) + c + salt, rate)


def rand_keep_global(shape, seed, rate, batch_offset=0, head_offset=0,
                     q_offset=0, k_offset=0):
    """(b, h, sq, sk) version of :func:`_rand_keep_coords` —
    bit-identical to the dropout partial kernels' masks, for the
    einsum sequence-parallel paths and for tests reassembling the
    expected global mask."""
    u32 = functools.partial(jnp.asarray, dtype=jnp.uint32)
    bi = _u32of(batch_offset) \
        + jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    hi = _u32of(head_offset) \
        + jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    salt = (_u32of(seed) * u32(0x85EBCA6B)
            ^ bi * u32(0xC2B2AE35)
            ^ hi * u32(0x27D4EB2F))
    r = _u32of(q_offset) + jax.lax.broadcasted_iota(jnp.uint32, shape, 2)
    c = _u32of(k_offset) + jax.lax.broadcasted_iota(jnp.uint32, shape, 3)
    return _keep_from_x(r * u32(0x01000193) + c + salt, rate)


# --- forward ---------------------------------------------------------------

def _fwd_single_kernel(scale, a, causal, has_kvm, has_off, kpad, sq, sk,
                       *refs, drop=0.0, h=1, pack=False, window=None,
                       prefix=0):
    """Whole-(padded)-sequence-in-one-block forward: plain softmax, no
    online-correction carries (the default 1024 blocks put GPT s=1024
    and BERT s=512 here).  ``has_off``: a leading SMEM ref carries
    [q_offset, k_offset] GLOBAL positions for the causal mask (the
    ring-attention partial — offsets are traced, so the mask compare
    runs every call; VPU work is hidden behind the MXU).  ``drop``:
    after the (optional) off ref an SMEM [seed, head_offset, q_offset,
    k_offset] ref salts the coordinate-hash keep mask (the SP dropout
    route; dropout's own offsets are separate from ``has_off`` because
    non-causal ring blocks drop the causal offsets entirely).
    ``pack``: q/k/v blocks carry a d=64 head PAIR on 128 lanes and
    ``h`` counts head PAIRS; per-head scores come from the sigma
    rotation (see the module head-packing note) and softmax/masking/
    dropout/lse run per head; lse_ref carries 16 sublanes (head 2j on
    rows 0-7, 2j+1 on 8-15).  ``prefix`` (static): the first ``prefix``
    keys stand before the causal square and every query sees them --
    query row ``i`` sits at position ``prefix + i``."""
    if has_off:
        off_ref, *refs = refs
        qoff, koff = off_ref[0], off_ref[1]
    else:
        qoff, koff = prefix, 0
    if drop > 0.0:
        dsalt_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    if has_kvm:
        kvm_ref, o_ref, lse_ref = rest
    else:
        kvm_ref = None
        o_ref, lse_ref = rest
    q = q_ref[0]
    k = k_ref[0]
    # raw logits, fp32; packed: both heads via two full-width matmuls
    heads = _packed_scores(q, k) if pack \
        else (_dot(q, k, trans_b=True),)
    mask = None
    if causal:
        mask = _tri_mask(heads[0].shape, qoff, koff)
    if window is not None:
        mask = mask & _window_mask(heads[0].shape, qoff, koff, window)
    if kpad and not has_kvm:
        # _kvm8 zero-pads, so kv_mask already masks pad columns
        km = _kcol_mask(heads[0].shape, 0, sk)
        mask = km if mask is None else (mask & km)
    if has_kvm:
        vm = kvm_ref[0, 0, 0, :][None, :] > 0
        mask = vm if mask is None else (mask & vm)
    guard_dead = has_kvm or (has_off and causal)
    if drop > 0.0:
        bh_i = pl.program_id(0)
    stats = []
    pas = []
    for hh, s in enumerate(heads):
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        m = jnp.max(s, axis=1, keepdims=True)         # raw units
        p = jnp.exp2((s - m) * a)
        l = jnp.sum(p, axis=1, keepdims=True)
        if guard_dead:
            # fully-masked rows (all keys masked, or an offset block
            # whose keys are all in the causal future): m stayed at
            # _NEG so (s - m) = 0 and p = 1 spuriously; zero them via
            # the row max instead of a score-shaped select.
            dead = m <= _NEG * 0.5
            l = jnp.where(dead, 0.0, l)
        else:
            dead = None
        pa = p
        if drop > 0.0:
            # l stays undropped (normalization by the true denominator);
            # only the accumulated values drop — the lse-merge across
            # ring blocks then reproduces dense in-kernel dropout
            # exactly.  Packed: the GLOBAL head index salts each half.
            head_ix = dsalt_ref[1] + (2 * (bh_i % h) + hh if pack
                                      else bh_i % h)
            keep = _rand_keep_coords(p.shape, dsalt_ref[0], bh_i // h,
                                     head_ix, dsalt_ref[2],
                                     dsalt_ref[3], drop)
            pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
        stats.append((m, l, dead))
        pas.append(pa)
    if pack:
        acc = _packed_out(pas[0], pas[1], v_ref[0])
        (m0, l0, dead0), (m1, l1, dead1) = stats
        sl0 = jnp.where(l0 == 0.0, 1.0, l0)
        sl1 = jnp.where(l1 == 0.0, 1.0, l1)
        o = acc * _pack_lane_cols(1.0 / sl0, 1.0 / sl1, acc.shape[1])
        if guard_dead:
            o = jnp.where(_pack_lane_cols(dead0, dead1, acc.shape[1]),
                          0.0, o)
        o_ref[0] = o.astype(o_ref.dtype)
        half = lse_ref.shape[2] // 2
        tail = lse_ref.shape[3:]
        lse0 = m0 * scale + jnp.log(sl0)
        lse1 = m1 * scale + jnp.log(sl1)
        lse_ref[0, 0] = jnp.concatenate(
            [jnp.broadcast_to(lse0[:, 0][None, :], (half,) + tail),
             jnp.broadcast_to(lse1[:, 0][None, :], (half,) + tail)],
            axis=0)
        return
    acc = _dot(pas[0].astype(v_ref.dtype), v_ref[0])
    m, l, dead = stats[0]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o = acc / safe_l
    if guard_dead:
        o = jnp.where(dead, 0.0, o)
    o_ref[0] = o.astype(o_ref.dtype)
    lse = m * scale + jnp.log(safe_l)
    lse_ref[0, 0] = jnp.broadcast_to(lse[:, 0][None, :],
                                     lse_ref.shape[2:])


def _fwd_kernel(scale, a, causal, has_kvm, has_off, kpad, sq, sk, bq, bk,
                *refs, drop=0.0, h=1, pack=False, window=None, prefix=0):
    if has_off:
        off_ref, *refs = refs
        qoff, koff = off_ref[0], off_ref[1]
    else:
        qoff, koff = prefix, 0      # see _fwd_single_kernel
    if drop > 0.0:
        dsalt_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    if has_kvm:
        kvm_ref, o_ref, lse_ref, acc, m_sc, l_sc = rest
    else:
        kvm_ref = None
        o_ref, lse_ref, acc, m_sc, l_sc = rest
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    # program ids read OUTSIDE the pl.when bodies: inside them the
    # primitive sits in a cond branch that interpret mode cannot lower
    bh_i = pl.program_id(0) if drop > 0.0 else 0

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    run = (j * bk + koff <= i * bq + qoff + bq - 1) if causal \
        else (j >= 0)
    if window is not None:
        # a key block wholly behind the window of the block's first
        # query is skipped like one wholly in the causal future (and
        # not fetched: the index map clamps j into the band)
        run = run & (j * bk + koff + bk - 1 > i * bq + qoff - window)

    @pl.when(run)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        # raw logits, fp32; packed: two heads per program, m/l carries
        # in scratch column hh (the E blocked kernel's idiom)
        heads = _packed_scores(q, k) if pack \
            else (_dot(q, k, trans_b=True),)
        mask = None
        if causal:
            mask = _tri_mask(heads[0].shape, i * bq + qoff,
                             j * bk + koff)
        if window is not None:
            mask = mask & _window_mask(heads[0].shape, i * bq + qoff,
                                       j * bk + koff, window)
        if kpad and not has_kvm:
            # _kvm8 zero-pads, so kv_mask already masks pad columns
            km = _kcol_mask(heads[0].shape, j * bk, sk)
            mask = km if mask is None else (mask & km)
        if has_kvm:
            vm = kvm_ref[0, 0, 0, :][None, :] > 0
            mask = vm if mask is None else (mask & vm)
        pas, corrs = [], []
        for hh, s in enumerate(heads):
            if mask is not None:
                s = jnp.where(mask, s, _NEG)
            m_prev = m_sc[:, hh:hh + 1]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp2((m_prev - m_cur) * a)
            p = jnp.exp2((s - m_cur) * a)
            if has_kvm or (has_off and causal) or window is not None:
                # rows with every key masked so far keep m_cur = _NEG
                # and (s - m_cur) = 0 at masked entries — zero p
                # explicitly so such rows sum to l = 0 and emit exactly
                # 0 (matching the backward, where masked entries
                # recompute p = 0).  The has_off case: a q-block
                # straddling the k_offset boundary runs with some rows
                # entirely in the causal future.
                p = jnp.where(mask, p, 0.0)
            l_new = l_sc[:, hh:hh + 1] * corr \
                + jnp.sum(p, axis=1, keepdims=True)
            pa = p
            if drop > 0.0:
                # see _fwd_single_kernel: values drop, l does not;
                # packed salts by the GLOBAL head index of each half
                head_ix = dsalt_ref[1] + (2 * (bh_i % h) + hh if pack
                                          else bh_i % h)
                keep = _rand_keep_coords(
                    p.shape, dsalt_ref[0], bh_i // h, head_ix,
                    dsalt_ref[2] + i * bq, dsalt_ref[3] + j * bk, drop)
                pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
            pas.append(pa)
            corrs.append(corr)
            if pack:
                m_sc[:, hh:hh + 1] = m_cur
                l_sc[:, hh:hh + 1] = l_new
            else:
                m_sc[:] = jnp.broadcast_to(m_cur, m_sc.shape)
                l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)
        if pack:
            corr_w = _pack_lane_cols(corrs[0], corrs[1], acc.shape[1])
            acc[:] = acc[:] * corr_w \
                + _packed_out(pas[0], pas[1], v_ref[0])
        else:
            acc[:] = acc[:] * corrs[0] \
                + _dot(pas[0].astype(v_ref.dtype), v_ref[0])

    @pl.when(j == nk - 1)
    def _finish():
        if pack:
            l0 = l_sc[:, :1]
            l1 = l_sc[:, 1:2]
            sl0 = jnp.where(l0 == 0.0, 1.0, l0)   # fully-masked rows
            sl1 = jnp.where(l1 == 0.0, 1.0, l1)   # -> zeros
            inv = _pack_lane_cols(1.0 / sl0, 1.0 / sl1, acc.shape[1])
            o_ref[0] = (acc[:] * inv).astype(o_ref.dtype)
            half = lse_ref.shape[2] // 2
            tail = lse_ref.shape[3:]
            lse0 = m_sc[:, :1] * scale + jnp.log(sl0)
            lse1 = m_sc[:, 1:2] * scale + jnp.log(sl1)
            lse_ref[0, 0] = jnp.concatenate(
                [jnp.broadcast_to(lse0[:, 0][None, :], (half,) + tail),
                 jnp.broadcast_to(lse1[:, 0][None, :], (half,) + tail)],
                axis=0)
            return
        l = l_sc[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse = m_sc[:, :1] * scale + jnp.log(l)
        lse_ref[0, 0] = jnp.broadcast_to(lse[:, 0][None, :],
                                         lse_ref.shape[2:])


def _pad_to(x, axis, mult, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _kvm8(kv_mask, b, psk, bk):
    """(b, sk) key-validity mask -> (b, psk/bk, 8, bk) sublane-
    replicated fp32 blocks (same trick as :func:`_rows8`).  Pads with
    zeros (= masked) to ``psk`` EXACTLY — the packed path's padded
    length can exceed the next bk multiple of sk."""
    m = kv_mask.astype(jnp.float32)
    if m.shape[1] < psk:
        m = jnp.pad(m, ((0, 0), (0, psk - m.shape[1])))
    return jnp.broadcast_to(
        m.reshape(b, psk // bk, 1, bk), (b, psk // bk, 8, bk))


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, kv_mask=None,
               offsets=None, drop=0.0, dsalt=None, window=None, prefix=0):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    # a value width of its own (latent attention's expanded heads score
    # on 192 dims and carry 128): v, o and the accumulator take it
    dv = v.shape[-1]
    # grouped-query attention: ``groups`` query heads read each of k/v's
    # heads (query head j reads head j // groups), through the k/v
    # index maps alone -- no repeated copy of k or v is made
    groups = h // k.shape[1]
    pack = groups == 1 and dv == d and _use_head_packing(h, d)
    if pack:
        # d=64 head-pair packing (module note): adjacent heads share a
        # 128-lane tile; h counts PAIRS below, lse carries 2 sublane
        # groups per q-block and unpacks to per-head order at the end.
        q, k, v = (_pack_head_pairs(x) for x in (q, k, v))
        h, d = h // 2, 2 * d
        dv = d
    g = 2 if pack else 1
    block_q, block_k = _clamp_blocks(block_q, block_k, d)
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(128, sk))
    q3 = _pad_to(q.reshape(b * h, sq, d), 1, bq)
    k3 = _pad_to(k.reshape(b * h // groups, sk, d), 1, bk)
    v3 = _pad_to(v.reshape(b * h // groups, sk, dv), 1, bk)
    bh, psq, _ = q3.shape
    psk = k3.shape[1]
    nq, nk = psq // bq, psk // bk
    a = scale * _LOG2E
    kpad = psk != sk

    def _unpack(o, lse8):
        lse = lse8[:, :, 0, :].reshape(bh, psq)[:, :sq]
        if not pack:
            return o[:, :sq].reshape(b, h, sq, dv), lse
        o4 = _unpack_head_pairs(o[:, :sq].reshape(b, h, sq, d))
        lse1 = lse8[:, :, 8, :].reshape(bh, psq)[:, :sq]
        # (bh_pairs, 2, sq) flattens straight to global head order:
        # pair j holds heads 2j / 2j+1
        lse = jnp.stack([lse, lse1], axis=1).reshape(bh * 2, sq)
        return o4, lse

    has_kvm = kv_mask is not None
    has_off = offsets is not None and causal
    # the forward with a pooled prefix before its causal square (EVA's
    # prefill) carries a name of its own into the HLO and the trace
    name = "eva_flash_attention_fwd" if prefix else "flash_attention_fwd"
    if nq == 1 and nk == 1:
        qb_spec = pl.BlockSpec((1, psq, d), lambda b_: (b_, 0, 0),
                               memory_space=pltpu.VMEM)
        kb_spec = pl.BlockSpec((1, psk, d),
                               (lambda b_: (b_, 0, 0)) if groups == 1
                               else (lambda b_: (b_ // groups, 0, 0)),
                               memory_space=pltpu.VMEM)
        lse_spec = pl.BlockSpec((1, 1, 8 * g, bq),
                                lambda b_: (b_, 0, 0, 0),
                                memory_space=pltpu.VMEM)
        vb_spec, ob_spec = kb_spec, qb_spec
        if dv != d:
            vb_spec = pl.BlockSpec((1, psk, dv), kb_spec.index_map,
                                   memory_space=pltpu.VMEM)
            ob_spec = pl.BlockSpec((1, psq, dv), qb_spec.index_map,
                                   memory_space=pltpu.VMEM)
        in_specs = [qb_spec, kb_spec, vb_spec]
        operands = [q3, k3, v3]
        if drop > 0.0:
            in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.insert(0, dsalt)
        if has_off:
            in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.insert(0, offsets)
        if has_kvm:
            in_specs.append(pl.BlockSpec(
                (1, 1, 8, bk), lambda b_: (b_ // h, 0, 0, 0),
                memory_space=pltpu.VMEM))
            operands.append(_kvm8(kv_mask, b, psk, bk))
        o, lse8 = pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale, a, causal,
                              has_kvm, has_off, kpad, sq, sk,
                              drop=drop, h=h, pack=pack, window=window,
                              prefix=prefix),
            grid=(bh,),
            in_specs=in_specs,
            out_specs=[ob_spec, lse_spec],
            out_shape=[
                jax.ShapeDtypeStruct((bh, psq, dv), q.dtype),
                jax.ShapeDtypeStruct((bh, 1, 8 * g, bq), jnp.float32),
            ],
            name=name,
            interpret=_interpret(),
        )(*operands)
        return _unpack(o, lse8)

    q_spec = pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0),
                          memory_space=pltpu.VMEM)
    if groups == 1 and window is None:
        def k_map(b_, i, j):
            return (b_, j, 0)
    else:
        def k_map(b_, i, j):
            if window is not None:
                # hold j inside the band of key blocks this query block
                # attends to: a block outside it is not fetched again
                j = jnp.clip(j, jnp.maximum(i * bq - window + 1, 0) // bk,
                             (i * bq + bq - 1) // bk)
            return (b_ // groups, j, 0)
    k_spec = pl.BlockSpec((1, bk, d), k_map, memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, 1, 8 * g, bq),
                            lambda b_, i, j: (b_, i, 0, 0),
                            memory_space=pltpu.VMEM)
    v_spec, o_spec = k_spec, q_spec
    if dv != d:
        v_spec = pl.BlockSpec((1, bk, dv), k_map, memory_space=pltpu.VMEM)
        o_spec = pl.BlockSpec((1, bq, dv), q_spec.index_map,
                              memory_space=pltpu.VMEM)
    in_specs = [q_spec, k_spec, v_spec]
    operands = [q3, k3, v3]
    if drop > 0.0:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, dsalt)
    if has_off:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, offsets)
    if has_kvm:
        kvm_spec = pl.BlockSpec(
            (1, 1, 8, bk), lambda b_, i, j: (b_ // h, j, 0, 0),
            memory_space=pltpu.VMEM)
        in_specs.append(kvm_spec)
        operands.append(_kvm8(kv_mask, b, psk, bk))
    o, lse8 = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, a, causal, has_kvm,
                          has_off, kpad, sq, sk, bq, bk,
                          drop=drop, h=h, pack=pack, window=window,
                          prefix=prefix),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, psq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, nq, 8 * g, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        name=name,
        interpret=_interpret(),
    )(*operands)
    return _unpack(o, lse8)


def _flash_fwd_packed(qkv, b, h, scale, causal, block_q, block_k,
                      kv_mask=None):
    """Self-attention forward over PACKED qkv (3*b*h, s, d): q/k/v are
    row-ranges of one contiguous array, read via index-map offsets into
    the SAME operand — no per-tensor relayout copies at the custom-call
    boundary (measured 7.5 ms/step of pure (b,h,s,d) layout copies at
    GPT-345M with the unpacked entry)."""
    bh = b * h
    s, d = qkv.shape[1], qkv.shape[2]
    block_q, block_k = _clamp_blocks(block_q, block_k, d)
    # clamp both blocks to s rounded up to the 128-lane grain: an
    # s-sized bq next to a 128-floored bk would make lcm(bq, bk) — the
    # shared padded length both block grids must divide — blow up
    # (s=50 with default blocks: lcm(50, 128) = 3200).
    grain = -(-s // 128) * 128
    bq = min(block_q, grain)
    bk = min(block_k, grain)
    qkv3 = _pad_to(qkv, 1, math.lcm(bq, bk))
    ps = qkv3.shape[1]
    nq, nk = ps // bq, ps // bk
    a = scale * _LOG2E
    kpad = ps != s
    has_kvm = kv_mask is not None

    if nq == 1 and nk == 1:
        def blkspec(off):
            return pl.BlockSpec((1, ps, d),
                                lambda b_, o=off: (b_ + o, 0, 0),
                                memory_space=pltpu.VMEM)
        lse_spec = pl.BlockSpec((1, 1, 8, bq), lambda b_: (b_, 0, 0, 0),
                                memory_space=pltpu.VMEM)
        in_specs = [blkspec(0), blkspec(bh), blkspec(2 * bh)]
        operands = [qkv3, qkv3, qkv3]
        if has_kvm:
            in_specs.append(pl.BlockSpec(
                (1, 1, 8, bk), lambda b_: (b_ // h, 0, 0, 0),
                memory_space=pltpu.VMEM))
            operands.append(_kvm8(kv_mask, b, ps, bk))
        o_spec = pl.BlockSpec((1, ps, d), lambda b_: (b_, 0, 0),
                              memory_space=pltpu.VMEM)
        o, lse8 = pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale, a, causal,
                              has_kvm, False, kpad, s, s),
            grid=(bh,),
            in_specs=in_specs,
            out_specs=[o_spec, lse_spec],
            out_shape=[
                jax.ShapeDtypeStruct((bh, ps, d), qkv.dtype),
                jax.ShapeDtypeStruct((bh, 1, 8, bq), jnp.float32),
            ],
            name="flash_attention_fwd",
            interpret=_interpret(),
        )(*operands)
        lse = lse8[:, :, 0, :].reshape(bh, ps)[:, :s]
        return o[:, :s], lse

    def qspec(off):
        return pl.BlockSpec((1, bq, d),
                            lambda b_, i, j, o=off: (b_ + o, i, 0),
                            memory_space=pltpu.VMEM)

    def kspec(off):
        return pl.BlockSpec((1, bk, d),
                            lambda b_, i, j, o=off: (b_ + o, j, 0),
                            memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, 1, 8, bq), lambda b_, i, j: (b_, i, 0, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [qspec(0), kspec(bh), kspec(2 * bh)]
    operands = [qkv3, qkv3, qkv3]
    if has_kvm:
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bk), lambda b_, i, j: (b_ // h, j, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(_kvm8(kv_mask, b, ps, bk))
    o, lse8 = pl.pallas_call(
        functools.partial(_fwd_kernel, scale, a, causal, has_kvm,
                          False, kpad, s, s, bq, bk),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[qspec(0), lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, ps, d), qkv.dtype),
            jax.ShapeDtypeStruct((bh, nq, 8, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=_interpret(),
    )(*operands)
    lse = lse8[:, :, 0, :].reshape(bh, ps)[:, :s]
    return o[:, :s], lse


# --- backward --------------------------------------------------------------
#
# All backward kernels recompute p as exp2(s*a - lse2) where
# lse2 = lse*log2(e) is pre-scaled OUTSIDE the kernel and q-padded rows
# get lse2 = +BIG (p underflows to exactly 0 — no q-position masks).
# v arrives pre-multiplied by ``scale`` so ds = p*(dp' - delta') needs
# no trailing ``*scale`` (delta' = delta*scale, also outside).  k-padded
# columns keep a (static, unaligned-only) mask: their s is 0 so
# p = exp2(-lse2) which can overflow to inf when lse is very negative,
# and inf * the zero k-pad rows would NaN dq.  The kv_mask path needs
# no kpad mask — _kvm8 zero-pads, masking pad columns for free.

def _bwd_dq_kernel(a, vscale, causal, has_kvm, has_off, kpad, sq, sk,
                   bq, bk, *refs, drop=0.0, h=1, pack=False):
    if has_off:
        off_ref, *refs = refs
        qoff, koff = off_ref[0], off_ref[1]
    else:
        qoff = koff = 0
    if drop > 0.0:
        dsalt_ref, *refs = refs
    q_ref, k_ref, v_ref, do_ref, lse2_ref, delta_ref, *rest = refs
    if has_kvm:
        kvm_ref, dq_ref, dq_acc = rest
    else:
        kvm_ref = None
        dq_ref, dq_acc = rest
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    bh_i = pl.program_id(0) if drop > 0.0 else 0   # see _fwd_kernel

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (j * bk + koff <= i * bq + qoff + bq - 1) if causal \
        else (j >= 0)

    @pl.when(run)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        heads = _packed_scores(q, k) if pack \
            else (_dot(q, k, trans_b=True),)
        mask = None
        if causal:
            mask = _tri_mask(heads[0].shape, i * bq + qoff,
                             j * bk + koff)
        if kpad and not has_kvm:
            km = _kcol_mask(heads[0].shape, j * bk, sk)
            mask = km if mask is None else (mask & km)
        if has_kvm:
            vm = kvm_ref[0, 0, 0, :][None, :] > 0
            mask = vm if mask is None else (mask & vm)
        vs = v_ref[0] * jnp.asarray(vscale, v_ref.dtype)
        dps = _packed_scores(do_ref[0], vs) if pack \
            else (_dot(do_ref[0], vs, trans_b=True),)
        dss = []
        for hh, (s, dp) in enumerate(zip(heads, dps)):
            lse2 = lse2_ref[0, 0, 8 * hh, :][:, None]
            arg = s * a - lse2
            if mask is not None:
                arg = jnp.where(mask, arg, _NEG)
            p = jnp.exp2(arg)
            if drop > 0.0:
                # regenerate the forward's keep mask from the same
                # global coordinates; ds = p*(keep*dp/(1-r) - delta)
                head_ix = dsalt_ref[1] + (2 * (bh_i % h) + hh if pack
                                          else bh_i % h)
                keep = _rand_keep_coords(
                    p.shape, dsalt_ref[0], bh_i // h, head_ix,
                    dsalt_ref[2] + i * bq, dsalt_ref[3] + j * bk, drop)
                dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - drop))
            delta = delta_ref[0, 0, 8 * hh, :][:, None]
            dss.append(p * (dp - delta))
        if pack:
            dq_acc[:] += _packed_out(dss[0], dss[1], k)
        else:
            dq_acc[:] += _dot(dss[0].astype(k.dtype), k)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(a, vscale, causal, has_kvm, has_off, kpad, sq, sk,
                    bq, bk, *refs, drop=0.0, h=1, pack=False):
    if has_off:
        off_ref, *refs = refs
        qoff, koff = off_ref[0], off_ref[1]
    else:
        qoff = koff = 0
    if drop > 0.0:
        dsalt_ref, *refs = refs
    q_ref, k_ref, v_ref, do_ref, lse2_ref, delta_ref, *rest = refs
    if has_kvm:
        kvm_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        kvm_ref = None
        dk_ref, dv_ref, dk_acc, dv_acc = rest
    i = pl.program_id(1)   # k block
    j = pl.program_id(2)   # q block
    nq = pl.num_programs(2)
    bh_i = pl.program_id(0) if drop > 0.0 else 0   # see _fwd_kernel

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (j * bq + qoff + bq - 1 >= i * bk + koff) if causal \
        else (j >= 0)

    @pl.when(run)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        heads = _packed_scores(q, k) if pack \
            else (_dot(q, k, trans_b=True),)          # (bq, bk)
        mask = None
        if causal:
            mask = _tri_mask(heads[0].shape, j * bq + qoff,
                             i * bk + koff)
        if kpad and not has_kvm:
            km = _kcol_mask(heads[0].shape, i * bk, sk)
            mask = km if mask is None else (mask & km)
        if has_kvm:
            vm = kvm_ref[0, 0, 0, :][None, :] > 0
            mask = vm if mask is None else (mask & vm)
        vs = v_ref[0] * jnp.asarray(vscale, v_ref.dtype)
        dps = _packed_scores(do, vs) if pack \
            else (_dot(do, vs, trans_b=True),)
        pas, dss = [], []
        for hh, (s, dp) in enumerate(zip(heads, dps)):
            lse2 = lse2_ref[0, 0, 8 * hh, :][:, None]
            arg = s * a - lse2
            if mask is not None:
                arg = jnp.where(mask, arg, _NEG)
            p = jnp.exp2(arg)
            pa = p
            if drop > 0.0:
                # rows are q-block j, cols k-block i on this side — the
                # coordinate hash makes the orientation swap free
                head_ix = dsalt_ref[1] + (2 * (bh_i % h) + hh if pack
                                          else bh_i % h)
                keep = _rand_keep_coords(
                    p.shape, dsalt_ref[0], bh_i // h, head_ix,
                    dsalt_ref[2] + j * bq, dsalt_ref[3] + i * bk, drop)
                pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
                dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - drop))
            delta = delta_ref[0, 0, 8 * hh, :][:, None]
            pas.append(pa)
            dss.append(p * (dp - delta))              # (bq, bk)
        if pack:
            dv_acc[:] += _packed_out_t0(pas[0], pas[1], do)
            dk_acc[:] += _packed_out_t0(dss[0], dss[1], q)
        else:
            dv_acc[:] += _dot_t0(pas[0].astype(do.dtype), do)
            dk_acc[:] += _dot_t0(dss[0].astype(q.dtype), q)

    @pl.when(j == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _rows8(x2d, bq):
    """(bh, rows) -> (bh, rows/bq, 8, bq) sublane-replicated view."""
    bh, rows = x2d.shape
    return jnp.broadcast_to(
        x2d.reshape(bh, rows // bq, 1, bq), (bh, rows // bq, 8, bq))


def _rows16(x2d, bq):
    """Per-head (b*h, rows) sidebands -> the packed kernels' paired
    (b*h/2, rows/bq, 16, bq) layout: head 2j broadcast over sublanes
    0-7 of pair j, head 2j+1 over 8-15 (matching the packed forward's
    lse emission and the ``8 * hh`` row reads in the backwards)."""
    bh2, rows = x2d.shape
    x = x2d.reshape(bh2 // 2, 2, rows // bq, 1, bq)
    x = jnp.broadcast_to(x, (bh2 // 2, 2, rows // bq, 8, bq))
    return x.transpose(0, 2, 1, 3, 4) \
        .reshape(bh2 // 2, rows // bq, 16, bq)


def _bwd_fused_kernel(a, vscale, causal, has_kvm, has_off, kpad, sq, sk,
                      *refs, drop=0.0, h=1, pack=False):
    """Single-block backward: when the whole (padded) sequence fits one
    q-block and one k-block, dq/dk/dv come from ONE pass — the scores
    ``s`` and ``dp`` are computed once instead of once per kernel (the
    two-kernel flash backward recomputes both), removing 2 of the 7
    matmuls; the two it removes are the d-contracted (half-MXU-lane)
    ones, so the saving exceeds their FLOP share.  ``pack``: d=64 head
    pairs on 128 lanes (module note) — all five products run full-width
    via the sigma rotation, lse/delta ride 16-sublane blocks."""
    if has_off:
        off_ref, *refs = refs
        qoff, koff = off_ref[0], off_ref[1]
    else:
        qoff = koff = 0
    if drop > 0.0:
        dsalt_ref, *refs = refs
    q_ref, k_ref, v_ref, do_ref, lse2_ref, delta_ref, *rest = refs
    if has_kvm:
        kvm_ref, dq_ref, dk_ref, dv_ref = rest
    else:
        kvm_ref = None
        dq_ref, dk_ref, dv_ref = rest
    q = q_ref[0]
    k = k_ref[0]
    do = do_ref[0]
    heads = _packed_scores(q, k) if pack \
        else (_dot(q, k, trans_b=True),)              # (sq, sk) fp32
    # dp next: it does not depend on the softmax, so the VPU's
    # exp2/select work on p overlaps this MXU pass.
    vs = v_ref[0] * jnp.asarray(vscale, v_ref.dtype)
    dps = _packed_scores(do, vs) if pack \
        else (_dot(do, vs, trans_b=True),)
    mask = None
    if causal:
        mask = _tri_mask(heads[0].shape, qoff, koff)
    if kpad and not has_kvm:
        km = _kcol_mask(heads[0].shape, 0, sk)
        mask = km if mask is None else (mask & km)
    if has_kvm:
        vm = kvm_ref[0, 0, 0, :][None, :] > 0
        mask = vm if mask is None else (mask & vm)
    if drop > 0.0:
        bh_i = pl.program_id(0)
    pas, dss = [], []
    for hh, (s, dp) in enumerate(zip(heads, dps)):
        lse2 = lse2_ref[0, 0, 8 * hh, :][:, None]
        arg = s * a - lse2
        if mask is not None:
            arg = jnp.where(mask, arg, _NEG)
        p = jnp.exp2(arg)
        pa = p
        if drop > 0.0:
            head_ix = dsalt_ref[1] + (2 * (bh_i % h) + hh if pack
                                      else bh_i % h)
            keep = _rand_keep_coords(p.shape, dsalt_ref[0], bh_i // h,
                                     head_ix, dsalt_ref[2],
                                     dsalt_ref[3], drop)
            pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - drop))
        delta = delta_ref[0, 0, 8 * hh, :][:, None]
        pas.append(pa)
        dss.append(p * (dp - delta))
    if pack:
        dv_ref[0] = _packed_out_t0(pas[0], pas[1], do) \
            .astype(dv_ref.dtype)
        dq_ref[0] = _packed_out(dss[0], dss[1], k).astype(dq_ref.dtype)
        dk_ref[0] = _packed_out_t0(dss[0], dss[1], q) \
            .astype(dk_ref.dtype)
        return
    dv_ref[0] = _dot_t0(pas[0].astype(do.dtype), do).astype(dv_ref.dtype)
    dq_ref[0] = _dot(dss[0].astype(k.dtype), k).astype(dq_ref.dtype)
    dk_ref[0] = _dot_t0(dss[0].astype(q.dtype), q).astype(dk_ref.dtype)


def _flash_bwd(scale, causal, block_q, block_k, res, do, kv_mask=None,
               offsets=None, dlse=None, drop=0.0, dsalt=None):
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    pack = _use_head_packing(h, d)
    # delta scales by the SAME v.dtype-rounded constant the kernels
    # fold into v: a non-power-of-two scale (e.g. d=96) rounds in bf16,
    # and mixing rounded dp' with exact-scaled delta' would bias
    # ds = p*(dp'-delta') wherever dp ~ delta.  Computed BEFORE any
    # head packing: lse/delta sidebands stay per-head either way.
    scale_v = float(np.asarray(scale).astype(v.dtype))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(b * h, sq)
    if dlse is not None:
        # lse cotangent (the partial entry): dlse/ds_raw = scale*p, so
        # it folds into delta — ds = p*(dp' - (delta - dlse)*scale_v)
        delta = delta - dlse.reshape(b * h, sq)
    delta = delta * scale_v
    lse2 = lse * _LOG2E
    if pack:
        # d=64 head-pair packing (module note): operands to the packed
        # lane layout, sidebands to paired 16-sublane blocks
        q, k, v, do = (_pack_head_pairs(x) for x in (q, k, v, do))
        h, d = h // 2, 2 * d
    g = 2 if pack else 1
    block_q, block_k = _clamp_blocks(block_q, block_k, d)
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(128, sk))
    a = scale * _LOG2E
    q3 = _pad_to(q.reshape(b * h, sq, d), 1, bq)
    k3 = _pad_to(k.reshape(b * h, sk, d), 1, bk)
    # scale folds into v INSIDE the kernels (a (bk, d) multiply in
    # VMEM) so dp' = do (v*scale)^T and ds needs no score-shaped
    # *scale; doing it here instead would cost a whole-array
    # read+write pass per layer (measured ~1.4 ms/step at GPT-345M).
    vs3 = _pad_to(v.reshape(b * h, sk, d), 1, bk)
    do3 = _pad_to(do.reshape(b * h, sq, d), 1, bq)
    bh, psq, _ = q3.shape
    psk = k3.shape[1]
    nq, nk = psq // bq, psk // bk
    kpad = psk != sk

    delta = _pad_to(delta, 1, bq)
    # +BIG pad: q-padded rows recompute p = exp2(s*a - BIG) = 0, so
    # they contribute nothing to dk/dv and need no position masks.
    lse2_p = _pad_to(lse2, 1, bq, value=_BIG)
    rows = _rows16 if pack else _rows8
    lse8 = rows(lse2_p, bq)
    delta8 = rows(delta, bq)
    has_kvm = kv_mask is not None
    has_off = offsets is not None and causal
    kvm = _kvm8(kv_mask, b, psk, bk) if has_kvm else None

    def _unpack_grads(dq, dk, dv):
        dq = dq[:, :sq].reshape(b, h, sq, d)
        dk = dk[:, :sk].reshape(b, h, sk, d)
        dv = dv[:, :sk].reshape(b, h, sk, d)
        if pack:
            dq, dk, dv = (_unpack_head_pairs(x) for x in (dq, dk, dv))
        return dq, dk, dv

    if nq == 1 and nk == 1 and (d <= 64 or pack):
        # Single-block fast path (e.g. GPT-345M s=1024 at the default
        # 1024-blocks; ring-attention shards): one fused kernel, 5
        # matmuls instead of 7.  d <= 64 keeps VMEM ~10 MB
        # (2 score-shaped fp32 temps + 7 thin operands); the packed
        # path qualifies too — its _clamp_blocks-halved bq caps the
        # per-head temps at (512, 1024) while the operand lanes double.
        qb_spec = pl.BlockSpec((1, psq, d), lambda b_: (b_, 0, 0),
                               memory_space=pltpu.VMEM)
        kb_spec = pl.BlockSpec((1, psk, d), lambda b_: (b_, 0, 0),
                               memory_space=pltpu.VMEM)
        rb_spec = pl.BlockSpec((1, 1, 8 * g, bq),
                               lambda b_: (b_, 0, 0, 0),
                               memory_space=pltpu.VMEM)
        in_specs = [qb_spec, kb_spec, kb_spec, qb_spec, rb_spec,
                    rb_spec]
        operands = [q3, k3, vs3, do3, lse8, delta8]
        if drop > 0.0:
            in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.insert(0, dsalt)
        if has_off:
            in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
            operands.insert(0, offsets)
        if has_kvm:
            in_specs.append(pl.BlockSpec(
                (1, 1, 8, bk), lambda b_: (b_ // h, 0, 0, 0),
                memory_space=pltpu.VMEM))
            operands.append(kvm)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, a, scale, causal,
                              has_kvm, has_off, kpad, sq, sk,
                              drop=drop, h=h, pack=pack),
            grid=(bh,),
            in_specs=in_specs,
            out_specs=[qb_spec, kb_spec, kb_spec],
            out_shape=[jax.ShapeDtypeStruct((bh, psq, d), q.dtype),
                       jax.ShapeDtypeStruct((bh, psk, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, psk, d), v.dtype)],
            name="flash_attention_bwd",
            interpret=_interpret(),
        )(*operands)
        return _unpack_grads(dq, dk, dv)

    q_spec_i = pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0),
                            memory_space=pltpu.VMEM)
    k_spec_j = pl.BlockSpec((1, bk, d), lambda b_, i, j: (b_, j, 0),
                            memory_space=pltpu.VMEM)
    r_spec_i = pl.BlockSpec((1, 1, 8 * g, bq),
                            lambda b_, i, j: (b_, i, 0, 0),
                            memory_space=pltpu.VMEM)

    in_specs = [q_spec_i, k_spec_j, k_spec_j, q_spec_i, r_spec_i,
                r_spec_i]
    operands = [q3, k3, vs3, do3, lse8, delta8]
    if drop > 0.0:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, dsalt)
    if has_off:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, offsets)
    if has_kvm:
        # kv mask indexed by the K block (grid dim 2 here)
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bk), lambda b_, i, j: (b_ // h, j, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(kvm)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, a, scale, causal, has_kvm,
                          has_off, kpad, sq, sk, bq, bk,
                          drop=drop, h=h, pack=pack),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec_i,
        out_shape=jax.ShapeDtypeStruct((bh, psq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=_interpret(),
    )(*operands)

    q_spec_j = pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, j, 0),
                            memory_space=pltpu.VMEM)
    k_spec_i = pl.BlockSpec((1, bk, d), lambda b_, i, j: (b_, i, 0),
                            memory_space=pltpu.VMEM)
    r_spec_j = pl.BlockSpec((1, 1, 8 * g, bq),
                            lambda b_, i, j: (b_, j, 0, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [q_spec_j, k_spec_i, k_spec_i, q_spec_j, r_spec_j,
                r_spec_j]
    operands = [q3, k3, vs3, do3, lse8, delta8]
    if drop > 0.0:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, dsalt)
    if has_off:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, offsets)
    if has_kvm:
        # kv mask indexed by the K block (grid dim 1 here)
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bk), lambda b_, i, j: (b_ // h, i, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(kvm)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, a, scale, causal, has_kvm,
                          has_off, kpad, sq, sk, bq, bk,
                          drop=drop, h=h, pack=pack),
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=[k_spec_i, k_spec_i],
        out_shape=[jax.ShapeDtypeStruct((bh, psk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, psk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        name="flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(*operands)

    return _unpack_grads(dq, dk, dv)


def _flash_bwd_packed(scale, causal, block_q, block_k, res, do,
                      kv_mask=None):
    """Backward of :func:`_flash_fwd_packed`: the saved PACKED qkv is
    read three times through offset index maps (no q/k/v relayout
    copies); dq/dk/dv come back as one (3*b*h, s, d) array so the
    caller's qkv-cotangent transpose fuses with this concatenation."""
    qkv, o, lse, b, h = res
    bh = b * h
    s, d = qkv.shape[1], qkv.shape[2]
    block_q, block_k = _clamp_blocks(block_q, block_k, d)
    grain = -(-s // 128) * 128      # see _flash_fwd_packed
    bq = min(block_q, grain)
    bk = min(block_k, grain)
    a = scale * _LOG2E
    # everything q-indexed pads to the SAME ps as the packed qkv: the
    # q-block grid spans ps // bq blocks, and a shorter do/lse/delta
    # would alias real rows through Pallas' clamped block indexing.
    lcm = math.lcm(bq, bk)
    qkv3 = _pad_to(qkv, 1, lcm)
    do3 = _pad_to(do, 1, lcm)
    ps = qkv3.shape[1]
    nq, nk = ps // bq, ps // bk
    kpad = ps != s

    scale_v = float(np.asarray(scale).astype(qkv.dtype))  # see _flash_bwd
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1) * scale_v
    delta = _pad_to(delta, 1, lcm)
    lse2_p = _pad_to(lse * _LOG2E, 1, lcm, value=_BIG)
    lse8 = _rows8(lse2_p, bq)
    delta8 = _rows8(delta, bq)
    has_kvm = kv_mask is not None
    kvm = _kvm8(kv_mask, b, ps, bk) if has_kvm else None

    if nq == 1 and nk == 1 and d <= 64:
        def blkspec(off):
            return pl.BlockSpec((1, ps, d),
                                lambda b_, o_=off: (b_ + o_, 0, 0),
                                memory_space=pltpu.VMEM)
        ob_spec = pl.BlockSpec((1, ps, d), lambda b_: (b_, 0, 0),
                               memory_space=pltpu.VMEM)
        rb_spec = pl.BlockSpec((1, 1, 8, bq), lambda b_: (b_, 0, 0, 0),
                               memory_space=pltpu.VMEM)
        in_specs = [blkspec(0), blkspec(bh), blkspec(2 * bh), ob_spec,
                    rb_spec, rb_spec]
        operands = [qkv3, qkv3, qkv3, do3, lse8, delta8]
        if has_kvm:
            in_specs.append(pl.BlockSpec(
                (1, 1, 8, bk), lambda b_: (b_ // h, 0, 0, 0),
                memory_space=pltpu.VMEM))
            operands.append(kvm)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, a, scale, causal,
                              has_kvm, False, kpad, s, s),
            grid=(bh,),
            in_specs=in_specs,
            out_specs=[ob_spec, ob_spec, ob_spec],
            out_shape=[jax.ShapeDtypeStruct((bh, ps, d), qkv.dtype)] * 3,
            name="flash_attention_bwd",
            interpret=_interpret(),
        )(*operands)
        return jnp.concatenate([dq[:, :s], dk[:, :s], dv[:, :s]],
                               axis=0)

    def spec_q(off):
        return pl.BlockSpec((1, bq, d),
                            lambda b_, i, j, o_=off: (b_ + o_, i, 0),
                            memory_space=pltpu.VMEM)

    def spec_k(off):
        return pl.BlockSpec((1, bk, d),
                            lambda b_, i, j, o_=off: (b_ + o_, j, 0),
                            memory_space=pltpu.VMEM)
    r_spec_i = pl.BlockSpec((1, 1, 8, bq), lambda b_, i, j: (b_, i, 0, 0),
                            memory_space=pltpu.VMEM)
    # do3 is its own (bh, ps, d) operand; spec_q(0) indexes it too
    in_specs = [spec_q(0), spec_k(bh), spec_k(2 * bh), spec_q(0),
                r_spec_i, r_spec_i]
    operands = [qkv3, qkv3, qkv3, do3, lse8, delta8]
    if has_kvm:
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bk), lambda b_, i, j: (b_ // h, j, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(kvm)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, a, scale, causal, has_kvm,
                          False, kpad, s, s, bq, bk),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, ps, d), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=_interpret(),
    )(*operands)

    def spec_qj(off):
        return pl.BlockSpec((1, bq, d),
                            lambda b_, i, j, o_=off: (b_ + o_, j, 0),
                            memory_space=pltpu.VMEM)

    def spec_ki(off):
        return pl.BlockSpec((1, bk, d),
                            lambda b_, i, j, o_=off: (b_ + o_, i, 0),
                            memory_space=pltpu.VMEM)
    r_spec_j = pl.BlockSpec((1, 1, 8, bq), lambda b_, i, j: (b_, j, 0, 0),
                            memory_space=pltpu.VMEM)
    do_spec_j = pl.BlockSpec((1, bq, d), lambda b_, i, j: (b_, j, 0),
                             memory_space=pltpu.VMEM)
    out_ki = pl.BlockSpec((1, bk, d), lambda b_, i, j: (b_, i, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [spec_qj(0), spec_ki(bh), spec_ki(2 * bh), do_spec_j,
                r_spec_j, r_spec_j]
    operands = [qkv3, qkv3, qkv3, do3, lse8, delta8]
    if has_kvm:
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bk), lambda b_, i, j: (b_ // h, i, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(kvm)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, a, scale, causal, has_kvm,
                          False, kpad, s, s, bq, bk),
        grid=(bh, nk, nq),
        in_specs=in_specs,
        out_specs=[out_ki, out_ki],
        out_shape=[jax.ShapeDtypeStruct((bh, ps, d), qkv.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        name="flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(*operands)

    return jnp.concatenate([dq[:, :s], dk[:, :s], dv[:, :s]], axis=0)


# --- public API ------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_fused(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           scale: Optional[float] = None,
                           causal: bool = False,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K) -> jnp.ndarray:
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k)[0]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    scale: Optional[float] = None,
                    causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    kv_mask: Optional[jnp.ndarray] = None,
                    window: Optional[int] = None,
                    prefix: int = 0) -> jnp.ndarray:
    """Fused attention: softmax(q k^T * scale [masked]) v.

    Shapes: q (b, h, sq, d); k, v (b, h, sk, d).  ``scale`` defaults to
    1/sqrt(d).  ``kv_mask`` (b, sk), True/nonzero = attend, masks
    padding KEYS (the BERT padding-attention case) — a capability the
    reference's FMHA lacks entirely (seqlen<=512, no mask support,
    ref: setup.py:408-424); composes with ``causal``.  Inside
    shard_map manual axes the XLA reference path runs (Pallas calls
    cannot yet carry VMA types).

    d=64 with even ``h`` (the reference FMHA's native head size) runs
    the head-packed full-width kernels — two heads per 128-lane MXU
    tile, ~2x the half-width rate; ``APEX_TPU_FLASH_PACK_D64=0`` or
    :func:`set_head_packing` force the old path (module note).

    Forward only (serving's prefill): ``k``/``v`` with fewer heads than
    ``q`` is grouped-query attention (query head ``j`` reads head
    ``j // (h // hk)``), ``window`` with ``causal`` keeps for each
    query the ``window`` keys ending at its own position, whole key
    blocks outside that band neither fetched nor computed, and ``v``
    may have a last dimension of its own (latent attention's expanded
    heads: q, k of 192, v and the result of 128).  ``prefix`` (static,
    with ``causal``): ``k``/``v`` hold ``prefix`` rows before the causal
    square, seen by every query (query ``i`` sees keys ``<= prefix +
    i``): EVA's pooled rows of the earlier windows, ahead of the
    window's own keys; ``kv_mask`` then says which of them are there.
    None of the four has a backward pass yet.
    """
    from ._context import in_manual_axis_context
    from .._autocast_ctx import autocast_compute_dtype

    # under amp.autocast (O1/O4) this call site is whitelisted: cast
    # inputs to the compute dtype here, at trace time, because the
    # interpreter cannot re-bind the dtype-frozen custom_vjp body
    act = autocast_compute_dtype()
    if act is not None and q.dtype != act \
            and jnp.issubdtype(q.dtype, jnp.floating):
        q, k, v = (x.astype(act) for x in (q, k, v))
    if in_manual_axis_context(q, k, v):
        return mha_reference(q, k, v, scale=scale, causal=causal,
                             kv_mask=kv_mask, window=window, prefix=prefix)
    if prefix:
        if not causal or window is not None:
            raise ValueError("a prefix stands before a causal square, "
                             "and not beside a window")
        if scale is None:
            scale = q.shape[-1] ** -0.5
        return _flash_fwd(
            q, k, v, scale, True, block_q, block_k,
            kv_mask=None if kv_mask is None
            else kv_mask.astype(jnp.float32), prefix=prefix)[0]
    if window is not None or k.shape[1] != q.shape[1] \
            or v.shape[-1] != q.shape[-1]:
        if kv_mask is not None or (window is not None and not causal):
            raise ValueError("grouped heads, a window and a value width "
                             "of its own run the causal or the unmasked "
                             "forward only")
        if scale is None:
            scale = q.shape[-1] ** -0.5
        return _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          window=window)[0]
    if kv_mask is not None:
        return _flash_attention_masked(q, k, v,
                                       kv_mask.astype(jnp.float32),
                                       scale, causal, block_q, block_k)
    return _flash_attention_fused(q, k, v, scale, causal, block_q, block_k)


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, res, do):
    if scale is None:
        scale = res[0].shape[-1] ** -0.5
    return _flash_bwd(scale, causal, block_q, block_k, res, do)


_flash_attention_fused.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_masked(q, k, v, kv_mask, scale, causal,
                            block_q, block_k):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                      kv_mask=kv_mask)[0]


def _flash_masked_vjp_fwd(q, k, v, kv_mask, scale, causal, block_q,
                          block_k):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        kv_mask=kv_mask)
    return o, (q, k, v, o, lse, kv_mask)


def _flash_masked_vjp_bwd(scale, causal, block_q, block_k, res, do):
    q, k, v, o, lse, kv_mask = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    dq, dk, dv = _flash_bwd(scale, causal, block_q, block_k,
                            (q, k, v, o, lse), do, kv_mask=kv_mask)
    # the (float) mask is a constant of the computation
    return dq, dk, dv, jnp.zeros_like(kv_mask)


_flash_attention_masked.defvjp(_flash_masked_vjp_fwd,
                               _flash_masked_vjp_bwd)


# --- packed-qkv self-attention entry ---------------------------------------

def flash_attention_qkv(qkv: jnp.ndarray,
                        scale: Optional[float] = None,
                        causal: bool = False,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        kv_mask: Optional[jnp.ndarray] = None
                        ) -> jnp.ndarray:
    """Self-attention over PACKED projections: ``qkv`` (3, b, h, s, d),
    returns the context (b, h, s, d).

    .. warning:: **Measured to LOSE ~5 ms/step end-to-end at the
       framework's own bench shapes** (GPT-345M, ROUND3_NOTES): the big
       (3,b,h,s,d) transpose XLA emits to build the packed operand
       costs more than the three per-tensor relayout copies it
       replaces.  Prefer :func:`flash_attention_e` — the
       projection-native layout with ZERO boundary copies — for
       self-attention; use this entry only if your model already holds
       qkv in this exact packed layout (the kernels themselves time
       identically to the per-tensor entry).

    Inside the kernel q/k/v are row-ranges of one contiguous array read
    via index-map offsets.  Semantics match
    ``flash_attention(qkv[0], qkv[1], qkv[2], ...)``.
    """
    from ._context import in_manual_axis_context
    from .._autocast_ctx import autocast_compute_dtype

    # same autocast boundary contract as flash_attention (this entry's
    # documented semantics are flash_attention(qkv[0], qkv[1], qkv[2]))
    act = autocast_compute_dtype()
    if act is not None and qkv.dtype != act \
            and jnp.issubdtype(qkv.dtype, jnp.floating):
        qkv = qkv.astype(act)
    if in_manual_axis_context(qkv):
        return mha_reference(qkv[0], qkv[1], qkv[2], scale=scale,
                             causal=causal, kv_mask=kv_mask)
    if kv_mask is not None:
        return _flash_qkv_masked(qkv, kv_mask.astype(jnp.float32),
                                 scale, causal, block_q, block_k)
    return _flash_qkv_fused(qkv, scale, causal, block_q, block_k)


def _qkv_flat(qkv):
    three, b, h, s, d = qkv.shape
    assert three == 3, f"qkv leading dim must be 3, got {three}"
    return qkv.reshape(3 * b * h, s, d), b, h


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_qkv_fused(qkv, scale, causal, block_q, block_k):
    flat, b, h = _qkv_flat(qkv)
    if scale is None:
        scale = qkv.shape[-1] ** -0.5
    o, _ = _flash_fwd_packed(flat, b, h, scale, causal, block_q,
                             block_k)
    return o.reshape(b, h, *o.shape[1:])


def _flash_qkv_vjp_fwd(qkv, scale, causal, block_q, block_k):
    flat, b, h = _qkv_flat(qkv)
    if scale is None:
        scale = qkv.shape[-1] ** -0.5
    o, lse = _flash_fwd_packed(flat, b, h, scale, causal, block_q,
                               block_k)
    return o.reshape(b, h, *o.shape[1:]), (flat, o, lse, b, h)


def _flash_qkv_vjp_bwd(scale, causal, block_q, block_k, res, do):
    flat, o, lse, b, h = res
    if scale is None:
        scale = flat.shape[-1] ** -0.5
    dflat = _flash_bwd_packed(scale, causal, block_q, block_k,
                              (flat, o, lse, b, h),
                              do.reshape(b * h, *do.shape[2:]))
    return (dflat.reshape(3, b, h, *dflat.shape[1:]),)


_flash_qkv_fused.defvjp(_flash_qkv_vjp_fwd, _flash_qkv_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _flash_qkv_masked(qkv, kv_mask, scale, causal, block_q, block_k):
    flat, b, h = _qkv_flat(qkv)
    if scale is None:
        scale = qkv.shape[-1] ** -0.5
    o, _ = _flash_fwd_packed(flat, b, h, scale, causal, block_q,
                             block_k, kv_mask=kv_mask)
    return o.reshape(b, h, *o.shape[1:])


def _flash_qkv_masked_vjp_fwd(qkv, kv_mask, scale, causal, block_q,
                              block_k):
    flat, b, h = _qkv_flat(qkv)
    if scale is None:
        scale = qkv.shape[-1] ** -0.5
    o, lse = _flash_fwd_packed(flat, b, h, scale, causal, block_q,
                               block_k, kv_mask=kv_mask)
    return o.reshape(b, h, *o.shape[1:]), (flat, o, lse, b, h, kv_mask)


def _flash_qkv_masked_vjp_bwd(scale, causal, block_q, block_k, res, do):
    flat, o, lse, b, h, kv_mask = res
    if scale is None:
        scale = flat.shape[-1] ** -0.5
    dflat = _flash_bwd_packed(scale, causal, block_q, block_k,
                              (flat, o, lse, b, h),
                              do.reshape(b * h, *do.shape[2:]),
                              kv_mask=kv_mask)
    return (dflat.reshape(3, b, h, *dflat.shape[1:]),
            jnp.zeros_like(kv_mask))


_flash_qkv_masked.defvjp(_flash_qkv_masked_vjp_fwd,
                         _flash_qkv_masked_vjp_bwd)


# --- partial (o, lse) entry: ring / blockwise composition -------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_partial(q, k, v, offsets, scale, causal, block_q, block_k):
    """Dynamic-offset partial (the ring path); static-zero offsets take
    :func:`_flash_partial_nooff` instead."""
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        offsets=offsets)
    return o, lse.reshape(q.shape[0], q.shape[1], -1)


def _flash_partial_vjp_fwd(q, k, v, offsets, scale, causal, block_q,
                           block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        offsets=offsets)
    out = (o, lse.reshape(q.shape[0], q.shape[1], -1))
    return out, (q, k, v, o, lse, offsets)


def _flash_partial_vjp_bwd(scale, causal, block_q, block_k, res, cts):
    q, k, v, o, lse, offsets = res
    do, dlse = cts
    dq, dk, dv = _flash_bwd(scale, causal, block_q, block_k,
                            (q, k, v, o, lse), do, offsets=offsets,
                            dlse=dlse.reshape(lse.shape))
    return dq, dk, dv, np.zeros(offsets.shape, dtype=jax.dtypes.float0)


_flash_partial.defvjp(_flash_partial_vjp_fwd, _flash_partial_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_partial_nooff(q, k, v, scale, causal, block_q, block_k):
    """Static-zero-offset partial: same (o, lse) contract as
    :func:`_flash_partial` without the offsets operand (no dead input /
    float0 cotangent on the non-ring path, e.g. the Ulysses wrapper)."""
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return o, lse.reshape(q.shape[0], q.shape[1], -1)


def _flash_partial_nooff_vjp_fwd(q, k, v, scale, causal, block_q,
                                 block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    out = (o, lse.reshape(q.shape[0], q.shape[1], -1))
    return out, (q, k, v, o, lse)


def _flash_partial_nooff_vjp_bwd(scale, causal, block_q, block_k, res,
                                 cts):
    q, k, v, o, lse = res
    do, dlse = cts
    dq, dk, dv = _flash_bwd(scale, causal, block_q, block_k,
                            (q, k, v, o, lse), do,
                            dlse=dlse.reshape(lse.shape))
    return dq, dk, dv


_flash_partial_nooff.defvjp(_flash_partial_nooff_vjp_fwd,
                            _flash_partial_nooff_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_partial_drop(q, k, v, offsets, dsalt, scale, causal, drop,
                        block_q, block_k):
    """Partial with IN-KERNEL dropout: ``dsalt`` = int32[4] of
    [seed, head_offset, q_offset, k_offset] salting the coordinate-hash
    keep mask in GLOBAL positions — ring/Ulysses shards draw
    non-repeating windows of one global mask, and the lse merge of
    value-dropped partials reproduces dense in-kernel dropout exactly
    (l and lse stay undropped; see _fwd_single_kernel)."""
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        offsets=offsets, drop=drop, dsalt=dsalt)
    return o, lse.reshape(q.shape[0], q.shape[1], -1)


def _flash_partial_drop_vjp_fwd(q, k, v, offsets, dsalt, scale, causal,
                                drop, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        offsets=offsets, drop=drop, dsalt=dsalt)
    out = (o, lse.reshape(q.shape[0], q.shape[1], -1))
    return out, (q, k, v, o, lse, offsets, dsalt)


def _flash_partial_drop_vjp_bwd(scale, causal, drop, block_q, block_k,
                                res, cts):
    q, k, v, o, lse, offsets, dsalt = res
    do, dlse = cts
    dq, dk, dv = _flash_bwd(scale, causal, block_q, block_k,
                            (q, k, v, o, lse), do, offsets=offsets,
                            dlse=dlse.reshape(lse.shape), drop=drop,
                            dsalt=dsalt)
    return (dq, dk, dv,
            np.zeros(offsets.shape, dtype=jax.dtypes.float0),
            np.zeros(dsalt.shape, dtype=jax.dtypes.float0))


_flash_partial_drop.defvjp(_flash_partial_drop_vjp_fwd,
                           _flash_partial_drop_vjp_bwd)


def flash_attention_partial(q: jnp.ndarray, k: jnp.ndarray,
                            v: jnp.ndarray,
                            scale: Optional[float] = None,
                            causal: bool = False,
                            q_offset=0, k_offset=0,
                            block_q: int = DEFAULT_BLOCK_Q,
                            block_k: int = DEFAULT_BLOCK_K,
                            dropout_rate: float = 0.0,
                            dropout_seed=None,
                            head_offset=0):
    """Blockwise-attention PARTIAL: returns ``(o, lse)`` — the
    softmax-normalized context of q against THIS k/v block plus the
    per-row log-sum-exp — so callers can combine blocks exactly with
    the flash merge ``lse = logaddexp(lse1, lse2); o = o1*exp(lse1-lse)
    + o2*exp(lse2-lse)``.  This is the ring-attention building block
    (and the general two-level flash composition primitive).

    ``q_offset``/``k_offset`` (traced ints OK — they ride an SMEM
    scalar into the kernels) place the block in GLOBAL coordinates for
    ``causal``: row i is position ``q_offset + i``, key j is
    ``k_offset + j``.  Fully-future blocks produce o = 0 and lse ~
    -1e30 (annihilated by the merge).  Gradients flow through both
    outputs (the lse cotangent folds into the backward's delta term).

    Unlike :func:`flash_attention` there is NO automatic shard_map
    fallback: this entry is designed to run inside
    ``shard_map(..., check_vma=False)``, where Pallas calls are legal
    (with ``check_vma=True`` the custom call is rejected by JAX —
    use ``check_vma=False`` on the enclosing shard_map).

    ``dropout_rate`` applies IN-KERNEL attention dropout from a
    coordinate-hash keep mask in GLOBAL positions: bit-identical to
    :func:`rand_keep_global` evaluated at (``q_offset``,
    ``head_offset``, ``k_offset``), so sequence-parallel shards draw
    non-repeating windows of one global mask and the lse merge of the
    value-dropped partials equals dense in-kernel dropout exactly.
    ``dropout_seed``: non-negative int32 (traced OK; same contract as
    :func:`flash_attention_e`).  ``head_offset``: global index of head
    0 of this shard (the Ulysses head-sharded case).
    """
    from .._autocast_ctx import autocast_compute_dtype

    if scale is None:
        scale = q.shape[-1] ** -0.5
    act = autocast_compute_dtype()
    if act is not None and q.dtype != act \
            and jnp.issubdtype(q.dtype, jnp.floating):
        q, k, v = (x.astype(act) for x in (q, k, v))
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                             jnp.asarray(k_offset, jnp.int32)])
        dsalt = jnp.stack([jnp.asarray(dropout_seed, jnp.int32),
                           jnp.asarray(head_offset, jnp.int32),
                           jnp.asarray(q_offset, jnp.int32),
                           jnp.asarray(k_offset, jnp.int32)])
        return _flash_partial_drop(q, k, v, offsets, dsalt, scale,
                                   causal, float(dropout_rate),
                                   block_q, block_k)
    # static-zero offsets (e.g. Ulysses' plain full-sequence causal
    # local attention) take the static-mask kernels — the dynamic
    # SMEM-offset masks cost ~10% kernel time (ROUND3_NOTES)
    use_off = not (isinstance(q_offset, int) and q_offset == 0
                   and isinstance(k_offset, int) and k_offset == 0)
    if not use_off:
        return _flash_partial_nooff(q, k, v, scale, causal, block_q,
                                    block_k)
    offsets = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                         jnp.asarray(k_offset, jnp.int32)])
    return _flash_partial(q, k, v, offsets, scale, causal, block_q,
                          block_k)


# --- E-layout (head-interleaved) self-attention ----------------------------
#
# Consumes the qkv projection's NATIVE output layout — (b, s, h, 3d),
# lanes ordered [head][q(d) k(d) v(d)], exactly what
# ``qkv.reshape(b, s, h, 3*d)`` of a fused projection yields — via
# lane-blocked BlockSpecs, and emits the context as (b, s, h*d) plus (in
# the vjp) ONE dqkv cotangent in the same interleaved layout.  No
# (b, h, s, d) transpose and no dq/dk/dv concatenate exists anywhere on
# this path: XLA cannot fuse transposes into a custom call, so the
# per-tensor entry forces eight bf16[b,h,s,d] relayout copies per layer
# (measured ~14 ms/step at GPT-345M, ~16 ms at BERT-large).  Heads are
# sliced out of the wide block INSIDE the kernel — measured free on v5e
# (the head-group microbench beat the per-head grid: lane slices
# pipeline behind the MXU).
#
# One block: the whole (128-aligned) sequence in VMEM, ps <= 1024 (the
# fp32 score temporaries of the non-causal square set that bound).
# Causal, its kernels walk the lower triangle in row chunks of
# `_e_chunk(ps)` rows against keys [0, end of chunk): 10 of the 16
# (256, 256) tiles at ps=1024, none of the masked half.  Longer
# sequences take the blocked walk below — `flash_e_plan` tells callers
# what runs for a shape, `flash_e_supported` whether anything does.

_E_MAX_SEQ = 1024
# Blocked sequence walk: sequences whose 128-aligned padding exceeds
# _E_MAX_SEQ (one VMEM block) stream (bs, bs) tiles with online softmax
# instead of falling back to the transposing path (the fallback re-pays
# the ~14-16 ms/step of (b,h,s,d) relayout glue the E layout exists to
# kill).  The cap bounds the lse/delta sideband arrays, not VMEM —
# at s=32768/h=16 the (b, h, 8, ps) fp32 sidebands are 64 MB of HBM
# per batch row, a sane ceiling; the walk itself is shape-generic
# (hardware-verified blocked parity at s=16384 for d in {64, 128}).
_E_MAX_SEQ_BLOCKED = flag_int("APEX_TPU_FLASH_E_MAX_SEQ")
_E_BLOCK = flag_int("APEX_TPU_FLASH_E_BLOCK")  # registry enforces %128
# lane budget per head-group block (3*hg*d lanes): sized so the bwd's
# score-shaped fp32 temporaries (~10 MB at ps=1024) plus double-buffered
# qkv/do/dqkv blocks stay inside the 16 MB VMEM window.
_E_LANE_BUDGET = flag_int("APEX_TPU_FLASH_E_LANES")


def _pick_heads_per_group(h: int, d: int, ps: int,
                          drop: bool = False) -> Optional[int]:
    """Largest divisor of ``h`` with 3*hg*d lanes within budget, lane-
    aligned (3*hg*d % 128 == 0), and few enough unrolled heads that the
    per-head (ps, ps) fp32 score temporaries stay inside VMEM — Mosaic
    only partially reuses them across the unrolled loop (measured: hg=4
    at ps=1024/d=64 fits with ~2 MB slack; hg=16 at ps=1024/d=16 asks
    for 43.6 MB).  ``drop`` halves the temp budget: the in-kernel keep
    mask adds score-shaped uint32/f32 temporaries per head (measured:
    hg=4/ps=1024/d=64 with dropout overflows scoped VMEM by 600 KB on
    hardware).  None when no grouping qualifies (callers fall back to
    the blocked walk or the transposing path)."""
    cap = max(1, _E_LANE_BUDGET // (3 * d))
    budget = (2 if drop else 4) * 1024 * 1024
    cap = min(cap, max(1, budget // (ps * ps)))
    for hg in range(min(cap, h), 0, -1):
        if h % hg == 0 and (3 * hg * d) % 128 == 0:
            return hg
    return None


def _pick_heads_per_group_blocked(h: int, d: int, bs: int,
                                  drop: bool = False) -> Optional[int]:
    """Head grouping for the BLOCKED E walk: same lane constraints as
    :func:`_pick_heads_per_group`, but the score-temporary budget counts
    (bs, bs) tiles and halves (the combined backward keeps both the dq
    and dk/dv sides' temporaries live in one kernel).  ``drop`` halves
    it again for the keep-mask temporaries (same VMEM class the
    single-block picker budgets for; hg=4 at bs=512 with dropout is
    measured to fit on hardware — the halved cap keeps exactly that)."""
    cap = max(1, _E_LANE_BUDGET // (3 * d))
    budget = (1 if drop else 2) * 1024 * 1024
    cap = min(cap, max(1, budget // (bs * bs)))
    for hg in range(min(cap, h), 0, -1):
        if h % hg == 0 and (3 * hg * d) % 128 == 0:
            return hg
    return None


def _e_mode(s: int, h: int, d: int, drop: bool = False):
    """('single'|'blocked', hg) when the E-layout kernels can run this
    shape, else (None, reason) — the reason string is what fallback
    sites log.  Short sequences whose whole-block grouping misfits
    (e.g. tiny d where the unrolled (ps, ps) temps blow VMEM) still
    take the blocked walk — its (bs, bs) tiles admit more shapes.
    ``drop`` mirrors the kernels' dropout-halved temp budgets so the
    reported mode/hg are the ones that actually execute."""
    ps = -(-s // 128) * 128
    if ps <= _E_MAX_SEQ:
        hg = _pick_heads_per_group(h, d, ps, drop=drop)
        if hg is not None:
            return "single", hg
    if ps <= _E_MAX_SEQ_BLOCKED:
        hg = _pick_heads_per_group_blocked(h, d, min(_E_BLOCK, ps),
                                           drop=drop)
        if hg is not None:
            return "blocked", hg
        return None, (f"no head grouping for h={h} d={d} within the "
                      f"VMEM lane budget (need 3*hg*d lanes % 128 == 0)")
    return None, (f"padded seq {ps} > APEX_TPU_FLASH_E_MAX_SEQ="
                  f"{_E_MAX_SEQ_BLOCKED}")


def flash_e_supported(s: int, h: int, d: int) -> bool:
    return _e_mode(s, h, d)[0] is not None


def _e_chunk(ps: int, causal: bool) -> int:
    """Query rows a chunk of the one-block kernels' walk over the
    (ps, ps) score square.  A causal chunk at rows [lo, lo + c) needs
    keys [0, lo + c) alone, so the kernels compute the lower triangle
    in row chunks and never the masked half; inside one VMEM block a
    finer chunk costs neither grid steps nor online-softmax rescales.
    From the padded length alone: 256 where it divides (10 of 16 tiles
    at ps=1024), else 128; one chunk (the whole square) when non-causal
    or when ps has no second chunk.  Measured on v5e at b=8 h=16 d=64
    ps=1024, us a call forward + backward (PERF.md, PR 32): the square
    410 + 919, chunks of 512 330 + 691, of 256 350 + 636, of 128
    412 + 629: a chunk costs a fixed ~0.25 us a head beside its tiles,
    so 128's fewer tiles do not pay for its eight chunks.  At the
    other padded lengths forward + backward win everywhere but ps=384
    (+4.6%); the forward alone loses at 384-640 (PERF.md section 7)."""
    if not causal or ps <= 128:
        return ps
    return 256 if ps % 256 == 0 and ps > 256 else 128


class FlashEPlan(NamedTuple):
    """What :func:`flash_attention_e` runs for a shape: the kernels are
    static per shape, so their gauge is a plan and not a counter."""
    mode: Optional[str]      # 'single' | 'blocked' | None (transposing)
    hg: Optional[int]        # heads a grid step
    chunk: Optional[int]     # query rows a chunk (single) or tile (blocked)
    share: Optional[float]   # of the (ps, ps) score square computed


def flash_e_plan(s: int, h: int, d: int, causal: bool,
                 drop: bool = False) -> FlashEPlan:
    """The E-layout plan for ``(s, h, d, causal)``, from the arithmetic
    the drivers use (:func:`_e_mode`, :func:`_e_chunk`).  ``share`` is
    the part of the padded score square whose matmuls and softmax run:
    (n + 1) / 2n over n causal chunks or tiles, 1.0 non-causal.  The
    blocked walk is given by its backward's tiles; its forward may
    widen them (see :func:`_flash_fwd_e_blocked`)."""
    mode, hg = _e_mode(s, h, d, drop=drop)
    if mode is None:
        return FlashEPlan(None, None, None, None)
    ps = -(-s // 128) * 128
    if mode == "single":
        chunk = _e_chunk(ps, causal)
    else:
        chunk = min(_E_BLOCK, ps)
        ps = -(-ps // chunk) * chunk
    n = ps // chunk
    return FlashEPlan(mode, hg, chunk,
                      (n + 1) / (2 * n) if causal else 1.0)


def _rand_keep(shape, seed, salt_b, salt_head, salt_i, salt_j, rate,
               row0=0, width=None):
    """Deterministic dropout keep-mask from a counter-based hash
    (murmur3 fmix32 over per-element counters + call-site salts).
    ``shape`` may be the rows from ``row0`` on of the first
    ``shape[1]`` columns of a ``width``-wide tile (the causal row
    chunks): an element's bit is the whole tile's.

    Plain jnp uint32 ops — no pltpu PRNG — so the SAME bits come out on
    TPU hardware and in interpret mode, and the backward regenerates the
    forward's mask from the same ``(seed, batch, head, q-block,
    k-block)`` salt tuple instead of materializing an O(s^2) mask array
    (the reference's in-kernel philox dropout plays this role,
    ref: apex/contrib/csrc/multihead_attn/dropout.h)."""
    u32 = functools.partial(jnp.asarray, dtype=jnp.uint32)

    def _u(x):
        # int32 program ids / traced seeds: mask to non-negative before
        # the uint32 view so XLA's checked conversions cannot trap
        return jnp.bitwise_and(jnp.asarray(x, jnp.int32),
                               jnp.int32(0x7FFFFFFF)).astype(jnp.uint32)

    salt = (_u(seed) * u32(0x85EBCA6B)
            ^ _u(salt_b) * u32(0xC2B2AE35)
            ^ _u(salt_head) * u32(0x27D4EB2F)
            ^ _u(salt_i) * u32(0x165667B1)
            ^ _u(salt_j) * u32(0x9E3779B9))
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    if row0:
        r = r + u32(row0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    x = r * u32(width or shape[1]) + c + salt
    x = (x ^ (x >> 16)) * u32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * u32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # top 24 bits to [0, 1): bitcast to int32 before the float convert —
    # Mosaic has no uint32->f32 cast, and after >> 8 the sign bit is 0
    f = jax.lax.bitcast_convert_type(x >> 8, jnp.int32) \
        .astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    return f >= jnp.float32(rate)


def _fwd_e_kernel(scale, a, causal, has_kvm, drop, kpad, s_real, hg, d,
                  *refs):
    """One-block E-layout forward: the whole padded sequence of ``hg``
    heads a grid step.  Each head walks its score square in row chunks
    of :func:`_e_chunk` rows: causal chunk [lo, hi) multiplies its
    queries with keys [0, hi) alone and sees every key it may attend to
    at once, so its softmax is one pass (no running max, no rescale).
    Non-causal there is one chunk, the square."""
    if drop > 0.0:
        seed_ref, *refs = refs
    qkv_ref, *rest = refs
    if has_kvm:
        kvm_ref, o_ref, lse_ref = rest
    else:
        kvm_ref = None
        o_ref, lse_ref = rest
    blk = qkv_ref[0]                       # (ps, hg*3*d)
    ps = blk.shape[0]
    c = _e_chunk(ps, causal)
    if has_kvm:
        vm = kvm_ref[0, 0, 0, :][None, :] > 0
    bidx = pl.program_id(0)
    gidx = pl.program_id(1)
    # chunk-major: the same chunk of the hg heads back to back, equal
    # shapes whose MXU and VPU phases overlap best (v5e, ps=1024, c=256:
    # 331 us a call against 350 head-major; the backward, whose dk/dv
    # sums run along a head's chunks, reads 636 head-major against 643)
    for lo, j in itertools.product(range(0, ps, c), range(hg)):
        off = j * 3 * d
        hi = lo + c
        qh = blk[lo:hi, off:off + d]
        kh = blk[:hi, off + d:off + 2 * d]
        vh = blk[:hi, off + 2 * d:off + 3 * d]
        s = _dot(qh, kh, trans_b=True)     # (c, hi) raw logits, fp32
        mask = None
        if causal:
            mask = _tri_mask(s.shape, lo, 0)
        if kpad and not has_kvm and hi > s_real:
            km = _kcol_mask(s.shape, 0, s_real)
            mask = km if mask is None else (mask & km)
        if has_kvm:
            mask = vm[:, :hi] if mask is None else (mask & vm[:, :hi])
        if mask is not None:
            s = jnp.where(mask, s, _NEG)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp2((s - m) * a)
        l = jnp.sum(p, axis=1, keepdims=True)
        if has_kvm:
            dead = m <= _NEG * 0.5         # see _fwd_single_kernel
            l = jnp.where(dead, 0.0, l)
        pa = p
        if drop > 0.0:
            # l comes from the UNDROPPED p (normalization is by the true
            # softmax denominator); only the accumulated values drop.
            keep = _rand_keep(p.shape, seed_ref[0], bidx, gidx * hg + j,
                              0, 0, drop, row0=lo, width=ps)
            pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
        acc = _dot(pa.astype(blk.dtype), vh)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o = acc / safe_l
        if has_kvm:
            o = jnp.where(dead, 0.0, o)
        o_ref[0, lo:hi, j * d:(j + 1) * d] = o.astype(o_ref.dtype)
        lse = m * scale + jnp.log(safe_l)
        lse_ref[0, j, :, lo:hi] = jnp.broadcast_to(
            lse[:, 0][None, :], (lse_ref.shape[2], c))


# The E drivers are jitted: a train step calls each once a layer with
# the same shapes, and under the outer jit an inner one is traced and
# lowered once a program, not once a layer.  That Pallas -> Mosaic
# lowering is Python time on every start, compile cache hit or not, and
# the chunked causal bodies have four times the equations (PERF.md,
# PR 28 and PR 32).  ``interpret`` is an argument so that it keys the
# trace: the callers below read it at call time.
_jit_e_driver = functools.partial(
    jax.jit, static_argnames=("h", "scale", "causal", "drop", "interpret"))


def _flash_fwd_e(qkv_e, h, scale, causal, kv_mask=None, drop=0.0,
                 seed=None):
    return _fwd_e_driver(qkv_e, kv_mask, seed, h=h, scale=scale,
                         causal=causal, drop=drop, interpret=_interpret())


def _flash_bwd_e(h, scale, causal, res, do, kv_mask=None, drop=0.0,
                 seed=None):
    qkv3, o3, lse = res                    # qkv3/o3 already ps-padded
    return _bwd_e_driver(qkv3, o3, lse, do, kv_mask, seed, h=h,
                         scale=scale, causal=causal, drop=drop,
                         interpret=_interpret())


@_jit_e_driver
def _fwd_e_driver(qkv_e, kv_mask, seed, h, scale, causal, drop,
                  interpret):
    b, s, width = qkv_e.shape
    d = width // (3 * h)
    ps = -(-s // 128) * 128
    hg = _pick_heads_per_group(h, d, ps, drop=drop > 0.0) \
        if ps <= _E_MAX_SEQ else None
    if hg is None:                   # matches _e_mode's 'blocked' arm
        return _flash_fwd_e_blocked(qkv_e, h, scale, causal, kv_mask,
                                    drop, seed, interpret)
    g = h // hg
    qkv3 = _pad_to(qkv_e, 1, ps)
    a = scale * _LOG2E
    kpad = ps != s
    has_kvm = kv_mask is not None

    qkv_spec = pl.BlockSpec((1, ps, hg * 3 * d),
                            lambda b_, g_: (b_, 0, g_),
                            memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, ps, hg * d), lambda b_, g_: (b_, 0, g_),
                          memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, hg, 8, ps),
                            lambda b_, g_: (b_, g_, 0, 0),
                            memory_space=pltpu.VMEM)
    in_specs = []
    operands = []
    if drop > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(seed, jnp.int32).reshape(1))
    in_specs.append(qkv_spec)
    operands.append(qkv3)
    if has_kvm:
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, ps), lambda b_, g_: (b_, 0, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(_kvm8(kv_mask, b, ps, ps))
    o, lse8 = pl.pallas_call(
        functools.partial(_fwd_e_kernel, scale, a, causal, has_kvm,
                          drop, kpad, s, hg, d),
        grid=(b, g),
        in_specs=in_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, ps, h * d), qkv_e.dtype),
            jax.ShapeDtypeStruct((b, h, 8, ps), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(*operands)
    lse = lse8[:, :, 0, :s]                # (b, h, s)
    return o[:, :s], lse


def _fwd_e_blocked_kernel(scale, a, causal, has_kvm, drop, kpad, s_real,
                          hg, d, bs, *refs):
    """Blocked E-layout forward: grid (b, g, i, j) walks (bs, bs) tiles
    with the online-softmax recurrence of :func:`_fwd_kernel`, but over
    the head-interleaved lane layout — q rows come from sequence-block
    ``i`` and k/v rows from block ``j`` of the SAME (b, ps, hg*3d)
    operand.  Per-head m/l carries live in single-lane columns of one
    (bs, 128) scratch."""
    if drop > 0.0:
        seed_ref, *refs = refs
    qkv_q_ref, qkv_k_ref, *rest = refs
    if has_kvm:
        kvm_ref, o_ref, lse_ref, acc, m_sc, l_sc = rest
    else:
        kvm_ref = None
        o_ref, lse_ref, acc, m_sc, l_sc = rest
    bidx = pl.program_id(0)
    gidx = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, _NEG)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    run = (j * bs <= i * bs + bs - 1) if causal else (j >= 0)

    @pl.when(run)
    def _block():
        qblk = qkv_q_ref[0]                # (bs, hg*3d)
        kblk = qkv_k_ref[0]
        if has_kvm:
            vm = kvm_ref[0, 0, 0, :][None, :] > 0
        for jh in range(hg):
            off = jh * 3 * d
            qh = qblk[:, off:off + d]
            kh = kblk[:, off + d:off + 2 * d]
            vh = kblk[:, off + 2 * d:off + 3 * d]
            s = _dot(qh, kh, trans_b=True)
            mask = None
            if causal:
                mask = _tri_mask(s.shape, i * bs, j * bs)
            if kpad and not has_kvm:
                km = _kcol_mask(s.shape, j * bs, s_real)
                mask = km if mask is None else (mask & km)
            if has_kvm:
                mask = vm if mask is None else (mask & vm)
            if mask is not None:
                s = jnp.where(mask, s, _NEG)
            m_prev = m_sc[:, jh:jh + 1]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp2((m_prev - m_cur) * a)
            p = jnp.exp2((s - m_cur) * a)
            if has_kvm:
                p = jnp.where(mask, p, 0.0)    # see _fwd_kernel
            l_new = l_sc[:, jh:jh + 1] * corr \
                + jnp.sum(p, axis=1, keepdims=True)
            pa = p
            if drop > 0.0:
                keep = _rand_keep(p.shape, seed_ref[0], bidx,
                                  gidx * hg + jh, i, j, drop)
                pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
            sl = slice(jh * d, (jh + 1) * d)
            acc[:, sl] = acc[:, sl] * corr \
                + _dot(pa.astype(qblk.dtype), vh)
            m_sc[:, jh:jh + 1] = m_cur
            l_sc[:, jh:jh + 1] = l_new

    @pl.when(j == nk - 1)
    def _finish():
        for jh in range(hg):
            l = l_sc[:, jh:jh + 1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o = acc[:, jh * d:(jh + 1) * d] / safe_l
            if has_kvm:
                dead = m_sc[:, jh:jh + 1] <= _NEG * 0.5
                o = jnp.where(dead, 0.0, o)
            o_ref[0, :, jh * d:(jh + 1) * d] = o.astype(o_ref.dtype)
            lse = m_sc[:, jh:jh + 1] * scale + jnp.log(safe_l)
            lse_ref[0, jh] = jnp.broadcast_to(lse[:, 0][None, :],
                                              lse_ref.shape[2:])


def _flash_fwd_e_blocked(qkv_e, h, scale, causal, kv_mask, drop, seed,
                         interpret):
    b, s, width = qkv_e.shape
    d = width // (3 * h)
    ps128 = -(-s // 128) * 128
    bs = min(_E_BLOCK, ps128)
    # Forward-only block widening: with one live score temp per head the
    # forward affords 1024-wide blocks (half the online-softmax carries;
    # measured: s=2048 E substep 2.35 vs 3.16 ms transposing after this,
    # from a dead-even tie at 512 blocks) — but dropout pins the forward
    # to the backward's block size so the counter-hash keep masks tile
    # identically in both directions, and d=128 stays at 512 (the
    # 1024-block d=128 kernel fails TPU compile; at 512 it already runs
    # 88 TF/s vs 41 transposing at Llama shape).
    if drop == 0.0 and d <= 64 and ps128 % 1024 == 0 \
            and _pick_heads_per_group_blocked(h, d, 1024) is not None:
        bs = 1024
        hg = _pick_heads_per_group_blocked(h, d, 1024)
    else:
        hg = _pick_heads_per_group_blocked(h, d, bs, drop=drop > 0.0)
    if hg is None:
        raise ValueError(
            f"blocked E-layout kernel cannot run h={h} d={d} bs={bs} "
            f"(no head grouping with 3*hg*d lanes % 128 == 0 inside "
            f"the VMEM budget); route through flash_attention_e, which "
            f"checks _e_mode and falls back")
    g = h // hg
    qkv3 = _pad_to(qkv_e, 1, bs)
    ps = qkv3.shape[1]
    nb = ps // bs
    a = scale * _LOG2E
    kpad = ps != s
    has_kvm = kv_mask is not None

    qkv_q_spec = pl.BlockSpec((1, bs, hg * 3 * d),
                              lambda b_, g_, i, j: (b_, i, g_),
                              memory_space=pltpu.VMEM)
    qkv_k_spec = pl.BlockSpec((1, bs, hg * 3 * d),
                              lambda b_, g_, i, j: (b_, j, g_),
                              memory_space=pltpu.VMEM)
    o_spec = pl.BlockSpec((1, bs, hg * d),
                          lambda b_, g_, i, j: (b_, i, g_),
                          memory_space=pltpu.VMEM)
    lse_spec = pl.BlockSpec((1, hg, 8, bs),
                            lambda b_, g_, i, j: (b_, g_, 0, i),
                            memory_space=pltpu.VMEM)
    in_specs = []
    operands = []
    if drop > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(seed, jnp.int32).reshape(1))
    in_specs += [qkv_q_spec, qkv_k_spec]
    operands += [qkv3, qkv3]
    if has_kvm:
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bs), lambda b_, g_, i, j: (b_, j, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(_kvm8(kv_mask, b, ps, bs))
    o, lse8 = pl.pallas_call(
        functools.partial(_fwd_e_blocked_kernel, scale, a, causal,
                          has_kvm, drop, kpad, s, hg, d, bs),
        grid=(b, g, nb, nb),
        in_specs=in_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, ps, h * d), qkv_e.dtype),
            jax.ShapeDtypeStruct((b, h, 8, ps), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, hg * d), jnp.float32),
            pltpu.VMEM((bs, 128), jnp.float32),
            pltpu.VMEM((bs, 128), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(*operands)
    lse = lse8[:, :, 0, :s]                # (b, h, s)
    return o[:, :s], lse


def _add_to_head_rows(acc, part):
    """``acc`` with its rows added to the leading rows of ``part``,
    which has more: a causal chunk's dk/dv rows [0, hi) meet the sum of
    the chunks before it, which reached [0, lo).  The cut is a multiple
    of 128 rows, so slices and concatenate move no data."""
    n = acc.shape[0]
    return jnp.concatenate([acc + part[:n], part[n:]], axis=0)


def _bwd_e_kernel(a, vscale, causal, has_kvm, drop, kpad, s_real, hg, d,
                  *refs):
    """One-block E-layout backward, one kernel, five matmuls a chunk,
    ``s`` recomputed once: the row chunks of :func:`_fwd_e_kernel`.
    A chunk's dq rows are final and stored at once; its dk/dv reach
    keys [0, hi) and are summed over the chunks in fp32 values, cast
    and stored after a head's last chunk."""
    if drop > 0.0:
        seed_ref, *refs = refs
    qkv_ref, do_ref, lse2_ref, delta_ref, *rest = refs
    if has_kvm:
        kvm_ref, dqkv_ref = rest
    else:
        kvm_ref = None
        (dqkv_ref,) = rest
    blk = qkv_ref[0]                       # (ps, hg*3*d)
    do_blk = do_ref[0]                     # (ps, hg*d)
    ps = blk.shape[0]
    c = _e_chunk(ps, causal)
    if has_kvm:
        vm = kvm_ref[0, 0, 0, :][None, :] > 0
    bidx = pl.program_id(0)
    gidx = pl.program_id(1)
    for j, lo in itertools.product(range(hg), range(0, ps, c)):
        off = j * 3 * d
        hi = lo + c
        qh = blk[lo:hi, off:off + d]
        kh = blk[:hi, off + d:off + 2 * d]
        vh = blk[:hi, off + 2 * d:off + 3 * d]
        doh = do_blk[lo:hi, j * d:(j + 1) * d]
        s = _dot(qh, kh, trans_b=True)     # (c, hi)
        # NOTE: unlike _bwd_fused_kernel, dp is NOT hoisted before the
        # softmax here — on the non-causal square a third live fp32
        # score buffer puts the kernel ~124 KB over the VMEM stack limit
        # at hg=4/ps=1024.  The causal chunks have the room and read
        # 606 us a call for 636 with it (v5e, PERF.md PR 32): ROADMAP S3.
        lse2 = lse2_ref[0, j, 0, lo:hi][:, None]
        arg = s * a - lse2
        mask = None
        if causal:
            mask = _tri_mask(s.shape, lo, 0)
        if kpad and not has_kvm and hi > s_real:
            km = _kcol_mask(s.shape, 0, s_real)
            mask = km if mask is None else (mask & km)
        if has_kvm:
            mask = vm[:, :hi] if mask is None else (mask & vm[:, :hi])
        if mask is not None:
            arg = jnp.where(mask, arg, _NEG)
        p = jnp.exp2(arg)
        if drop > 0.0:
            # regenerate the forward's keep mask; dv consumes the
            # dropped/rescaled probabilities, ds the undropped p with
            # the mask applied to dp (dS = P*(dP@M/(1-r) - delta))
            keep = _rand_keep(p.shape, seed_ref[0], bidx, gidx * hg + j,
                              0, 0, drop, row0=lo, width=ps)
            pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
        else:
            pa = p
        dv_c = _dot_t0(pa.astype(doh.dtype), doh)      # (hi, d)
        vs = vh * jnp.asarray(vscale, vh.dtype)
        dp = _dot(doh, vs, trans_b=True)
        if drop > 0.0:
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - drop))
        delta = delta_ref[0, j, 0, lo:hi][:, None]
        ds = p * (dp - delta)
        dq = _dot(ds.astype(kh.dtype), kh)
        dk_c = _dot_t0(ds.astype(qh.dtype), qh)        # (hi, d)
        dqkv_ref[0, lo:hi, off:off + d] = dq.astype(dqkv_ref.dtype)
        if lo == 0:
            dk, dv = dk_c, dv_c
        else:
            dk = _add_to_head_rows(dk, dk_c)
            dv = _add_to_head_rows(dv, dv_c)
        if hi == ps:
            dqkv_ref[0, :, off + d:off + 2 * d] = dk.astype(dqkv_ref.dtype)
            dqkv_ref[0, :, off + 2 * d:off + 3 * d] = \
                dv.astype(dqkv_ref.dtype)


@_jit_e_driver
def _bwd_e_driver(qkv3, o3, lse, do, kv_mask, seed, h, scale, causal,
                  drop, interpret):
    b, ps, width = qkv3.shape
    s = do.shape[1]                        # qkv3/o3 are ps-padded
    d = width // (3 * h)
    hg = _pick_heads_per_group(h, d, ps, drop=drop > 0.0) \
        if ps <= _E_MAX_SEQ else None
    if hg is None:                   # same dispatch as _fwd_e_driver
        return _flash_bwd_e_blocked(qkv3, o3, lse, do, kv_mask, seed, h,
                                    scale, causal, drop, interpret)
    g = h // hg
    a = scale * _LOG2E
    kpad = ps != s
    has_kvm = kv_mask is not None

    do3 = _pad_to(do, 1, ps)
    scale_v = float(np.asarray(scale).astype(qkv3.dtype))  # see _flash_bwd
    delta = (do3.astype(jnp.float32) * o3.astype(jnp.float32)) \
        .reshape(b, ps, h, d).sum(-1).transpose(0, 2, 1) * scale_v
    delta8 = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, ps))
    lse2 = _pad_to(lse * _LOG2E, 2, ps, value=_BIG)        # (b, h, ps)
    lse28 = jnp.broadcast_to(lse2[:, :, None, :], (b, h, 8, ps))

    qkv_spec = pl.BlockSpec((1, ps, hg * 3 * d),
                            lambda b_, g_: (b_, 0, g_),
                            memory_space=pltpu.VMEM)
    do_spec = pl.BlockSpec((1, ps, hg * d), lambda b_, g_: (b_, 0, g_),
                           memory_space=pltpu.VMEM)
    r_spec = pl.BlockSpec((1, hg, 8, ps), lambda b_, g_: (b_, g_, 0, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [qkv_spec, do_spec, r_spec, r_spec]
    operands = [qkv3, do3, lse28, delta8]
    if drop > 0.0:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, jnp.asarray(seed, jnp.int32).reshape(1))
    if has_kvm:
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, ps), lambda b_, g_: (b_, 0, 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(_kvm8(kv_mask, b, ps, ps))
    dqkv = pl.pallas_call(
        functools.partial(_bwd_e_kernel, a, scale, causal, has_kvm,
                          drop, kpad, s, hg, d),
        grid=(b, g),
        in_specs=in_specs,
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b, ps, width), qkv3.dtype),
        name="flash_attention_bwd",
        interpret=interpret,
    )(*operands)
    return dqkv[:, :s]


def _bwd_e_blocked_kernel(a, vscale, causal, has_kvm, drop, kpad,
                          s_real, hg, d, bs, *refs):
    """Blocked E-layout backward, ONE kernel: grid (b, g, i, j) where
    ``i`` is the sequence block whose full-width dqkv tile this cell
    owns and ``j`` walks all sequence blocks.  Each cell accumulates
    BOTH sides into VMEM scratch:

    - dq side (q-block i vs k-block j, causal keeps j <= i):
      ds = p*(dp' - delta'),  dq_i += ds @ k_j
    - dk/dv side (q-block j vs k-block i, causal keeps j >= i):
      dv_i += p^T do_j,  dk_i += ds^T q_j

    Every (i, j) score tile is computed exactly twice across the grid —
    the same total as the classic two-kernel flash backward — but the
    output is ONE (bs, hg*3d) head-interleaved dqkv tile per i: no dq
    vs dk/dv split, no concatenate, zero relayout copies at the
    custom-call boundary (the whole point of the E layout)."""
    if drop > 0.0:
        seed_ref, *refs = refs
    (qkv_i_ref, qkv_j_ref, do_i_ref, do_j_ref, lse_i_ref, lse_j_ref,
     delta_i_ref, delta_j_ref, *rest) = refs
    if has_kvm:
        kvm_i_ref, kvm_j_ref, dqkv_ref, dq_acc, dk_acc, dv_acc = rest
    else:
        kvm_i_ref = kvm_j_ref = None
        dqkv_ref, dq_acc, dk_acc, dv_acc = rest
    bidx = pl.program_id(0)
    gidx = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    ns = pl.num_programs(3)
    inv = 1.0 / (1.0 - drop) if drop > 0.0 else 1.0

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run_dq = (j <= i) if causal else (j >= 0)
    run_dkv = (j >= i) if causal else (j >= 0)

    @pl.when(run_dq)
    def _dq_side():
        iblk = qkv_i_ref[0]
        jblk = qkv_j_ref[0]
        do_i = do_i_ref[0]
        if has_kvm:
            vm = kvm_j_ref[0, 0, 0, :][None, :] > 0
        for jh in range(hg):
            off = jh * 3 * d
            qh = iblk[:, off:off + d]
            kh = jblk[:, off + d:off + 2 * d]
            vh = jblk[:, off + 2 * d:off + 3 * d]
            doh = do_i[:, jh * d:(jh + 1) * d]
            s = _dot(qh, kh, trans_b=True)
            lse2 = lse_i_ref[0, jh, 0, :][:, None]
            arg = s * a - lse2
            mask = None
            if causal:
                mask = _tri_mask(s.shape, i * bs, j * bs)
            if kpad and not has_kvm:
                km = _kcol_mask(s.shape, j * bs, s_real)
                mask = km if mask is None else (mask & km)
            if has_kvm:
                mask = vm if mask is None else (mask & vm)
            if mask is not None:
                arg = jnp.where(mask, arg, _NEG)
            p = jnp.exp2(arg)
            vs = vh * jnp.asarray(vscale, vh.dtype)
            dp = _dot(doh, vs, trans_b=True)
            if drop > 0.0:
                keep = _rand_keep(p.shape, seed_ref[0], bidx,
                                  gidx * hg + jh, i, j, drop)
                dp = jnp.where(keep, dp, 0.0) * inv
            delta = delta_i_ref[0, jh, 0, :][:, None]
            ds = p * (dp - delta)
            sl = slice(jh * d, (jh + 1) * d)
            dq_acc[:, sl] = dq_acc[:, sl] + _dot(ds.astype(kh.dtype), kh)

    @pl.when(run_dkv)
    def _dkv_side():
        iblk = qkv_i_ref[0]
        jblk = qkv_j_ref[0]
        do_j = do_j_ref[0]
        if has_kvm:
            vm = kvm_i_ref[0, 0, 0, :][None, :] > 0
        for jh in range(hg):
            off = jh * 3 * d
            qh = jblk[:, off:off + d]              # q rows: block j
            kh = iblk[:, off + d:off + 2 * d]      # k rows: block i
            vh = iblk[:, off + 2 * d:off + 3 * d]
            doh = do_j[:, jh * d:(jh + 1) * d]
            s = _dot(qh, kh, trans_b=True)         # rows=q_j, cols=k_i
            lse2 = lse_j_ref[0, jh, 0, :][:, None]
            arg = s * a - lse2
            mask = None
            if causal:
                mask = _tri_mask(s.shape, j * bs, i * bs)
            if kpad and not has_kvm:
                km = _kcol_mask(s.shape, i * bs, s_real)
                mask = km if mask is None else (mask & km)
            if has_kvm:
                mask = vm if mask is None else (mask & vm)
            if mask is not None:
                arg = jnp.where(mask, arg, _NEG)
            p = jnp.exp2(arg)
            if drop > 0.0:
                # same salt orientation as the forward: (q-block,
                # k-block) = (j, i) on this side
                keep = _rand_keep(p.shape, seed_ref[0], bidx,
                                  gidx * hg + jh, j, i, drop)
                pa = jnp.where(keep, p, 0.0) * inv
            else:
                pa = p
            sl = slice(jh * d, (jh + 1) * d)
            dv_acc[:, sl] = dv_acc[:, sl] \
                + _dot_t0(pa.astype(doh.dtype), doh)
            vs = vh * jnp.asarray(vscale, vh.dtype)
            dp = _dot(doh, vs, trans_b=True)
            if drop > 0.0:
                dp = jnp.where(keep, dp, 0.0) * inv
            delta = delta_j_ref[0, jh, 0, :][:, None]
            ds = p * (dp - delta)
            dk_acc[:, sl] = dk_acc[:, sl] \
                + _dot_t0(ds.astype(qh.dtype), qh)

    @pl.when(j == ns - 1)
    def _finish():
        for jh in range(hg):
            off = jh * 3 * d
            sl = slice(jh * d, (jh + 1) * d)
            dqkv_ref[0, :, off:off + d] = \
                dq_acc[:, sl].astype(dqkv_ref.dtype)
            dqkv_ref[0, :, off + d:off + 2 * d] = \
                dk_acc[:, sl].astype(dqkv_ref.dtype)
            dqkv_ref[0, :, off + 2 * d:off + 3 * d] = \
                dv_acc[:, sl].astype(dqkv_ref.dtype)


def _flash_bwd_e_blocked(qkv3, o3, lse, do, kv_mask, seed, h, scale,
                         causal, drop, interpret):
    b, _, width = qkv3.shape               # 128-aligned from the vjp fwd
    s = do.shape[1]
    d = width // (3 * h)
    bs = min(_E_BLOCK, -(-s // 128) * 128)
    # residuals are 128-aligned; the blocked walk needs bs multiples
    qkv3 = _pad_to(qkv3, 1, bs)
    o3 = _pad_to(o3, 1, bs)
    ps = qkv3.shape[1]
    hg = _pick_heads_per_group_blocked(h, d, bs, drop=drop > 0.0)
    if hg is None:
        raise ValueError(
            f"blocked E-layout backward cannot run h={h} d={d} bs={bs} "
            f"(no head grouping with 3*hg*d lanes % 128 == 0 inside "
            f"the VMEM budget); route through flash_attention_e, which "
            f"checks _e_mode and falls back")
    g = h // hg
    nb = ps // bs
    a = scale * _LOG2E
    kpad = ps != s
    has_kvm = kv_mask is not None

    do3 = _pad_to(do, 1, ps)
    scale_v = float(np.asarray(scale).astype(qkv3.dtype))  # see _flash_bwd
    delta = (do3.astype(jnp.float32) * o3.astype(jnp.float32)) \
        .reshape(b, ps, h, d).sum(-1).transpose(0, 2, 1) * scale_v
    delta8 = jnp.broadcast_to(delta[:, :, None, :], (b, h, 8, ps))
    lse2 = _pad_to(lse * _LOG2E, 2, ps, value=_BIG)        # (b, h, ps)
    lse28 = jnp.broadcast_to(lse2[:, :, None, :], (b, h, 8, ps))

    def qkv_spec(which):
        return pl.BlockSpec(
            (1, bs, hg * 3 * d),
            (lambda b_, g_, i, j: (b_, i, g_)) if which == "i"
            else (lambda b_, g_, i, j: (b_, j, g_)),
            memory_space=pltpu.VMEM)

    def do_spec(which):
        return pl.BlockSpec(
            (1, bs, hg * d),
            (lambda b_, g_, i, j: (b_, i, g_)) if which == "i"
            else (lambda b_, g_, i, j: (b_, j, g_)),
            memory_space=pltpu.VMEM)

    def r_spec(which):
        return pl.BlockSpec(
            (1, hg, 8, bs),
            (lambda b_, g_, i, j: (b_, g_, 0, i)) if which == "i"
            else (lambda b_, g_, i, j: (b_, g_, 0, j)),
            memory_space=pltpu.VMEM)

    in_specs = [qkv_spec("i"), qkv_spec("j"), do_spec("i"), do_spec("j"),
                r_spec("i"), r_spec("j"), r_spec("i"), r_spec("j")]
    operands = [qkv3, qkv3, do3, do3, lse28, lse28, delta8, delta8]
    if drop > 0.0:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, jnp.asarray(seed, jnp.int32).reshape(1))
    if has_kvm:
        kvm = _kvm8(kv_mask, b, ps, bs)
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bs), lambda b_, g_, i, j: (b_, i, 0, 0),
            memory_space=pltpu.VMEM))
        in_specs.append(pl.BlockSpec(
            (1, 1, 8, bs), lambda b_, g_, i, j: (b_, j, 0, 0),
            memory_space=pltpu.VMEM))
        operands += [kvm, kvm]
    dqkv = pl.pallas_call(
        functools.partial(_bwd_e_blocked_kernel, a, scale, causal,
                          has_kvm, drop, kpad, s, hg, d, bs),
        grid=(b, g, nb, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bs, hg * 3 * d),
                               lambda b_, g_, i, j: (b_, i, g_),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, ps, width), qkv3.dtype),
        scratch_shapes=[
            pltpu.VMEM((bs, hg * d), jnp.float32),
            pltpu.VMEM((bs, hg * d), jnp.float32),
            pltpu.VMEM((bs, hg * d), jnp.float32),
        ],
        name="flash_attention_bwd",
        interpret=interpret,
    )(*operands)
    return dqkv[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_e_fused(qkv_e, h, scale, causal):
    return _flash_fwd_e(qkv_e, h, scale, causal)[0]


def _flash_e_vjp_fwd(qkv_e, h, scale, causal):
    ps = -(-qkv_e.shape[1] // 128) * 128
    o, lse = _flash_fwd_e(qkv_e, h, scale, causal)
    o3 = _pad_to(o, 1, ps)
    return o, (_pad_to(qkv_e, 1, ps), o3, lse)


def _flash_e_vjp_bwd(h, scale, causal, res, do):
    return (_flash_bwd_e(h, scale, causal, res, do),)


_flash_e_fused.defvjp(_flash_e_vjp_fwd, _flash_e_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _flash_e_masked(qkv_e, kv_mask, h, scale, causal):
    return _flash_fwd_e(qkv_e, h, scale, causal, kv_mask=kv_mask)[0]


def _flash_e_masked_vjp_fwd(qkv_e, kv_mask, h, scale, causal):
    ps = -(-qkv_e.shape[1] // 128) * 128
    o, lse = _flash_fwd_e(qkv_e, h, scale, causal, kv_mask=kv_mask)
    o3 = _pad_to(o, 1, ps)
    return o, (_pad_to(qkv_e, 1, ps), o3, lse, kv_mask)


def _flash_e_masked_vjp_bwd(h, scale, causal, res, do):
    *core, kv_mask = res
    dqkv = _flash_bwd_e(h, scale, causal, tuple(core), do,
                        kv_mask=kv_mask)
    return dqkv, jnp.zeros_like(kv_mask)


_flash_e_masked.defvjp(_flash_e_masked_vjp_fwd, _flash_e_masked_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _flash_e_drop(qkv_e, seed, h, scale, causal, rate):
    return _flash_fwd_e(qkv_e, h, scale, causal, drop=rate,
                        seed=seed)[0]


def _flash_e_drop_vjp_fwd(qkv_e, seed, h, scale, causal, rate):
    ps = -(-qkv_e.shape[1] // 128) * 128
    o, lse = _flash_fwd_e(qkv_e, h, scale, causal, drop=rate, seed=seed)
    o3 = _pad_to(o, 1, ps)
    return o, (_pad_to(qkv_e, 1, ps), o3, lse, seed)


def _flash_e_drop_vjp_bwd(h, scale, causal, rate, res, do):
    *core, seed = res
    dqkv = _flash_bwd_e(h, scale, causal, tuple(core), do, drop=rate,
                        seed=seed)
    return dqkv, np.zeros(jnp.shape(seed), dtype=jax.dtypes.float0)


_flash_e_drop.defvjp(_flash_e_drop_vjp_fwd, _flash_e_drop_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_e_masked_drop(qkv_e, kv_mask, seed, h, scale, causal, rate):
    return _flash_fwd_e(qkv_e, h, scale, causal, kv_mask=kv_mask,
                        drop=rate, seed=seed)[0]


def _flash_e_masked_drop_vjp_fwd(qkv_e, kv_mask, seed, h, scale, causal,
                                 rate):
    ps = -(-qkv_e.shape[1] // 128) * 128
    o, lse = _flash_fwd_e(qkv_e, h, scale, causal, kv_mask=kv_mask,
                          drop=rate, seed=seed)
    o3 = _pad_to(o, 1, ps)
    return o, (_pad_to(qkv_e, 1, ps), o3, lse, kv_mask, seed)


def _flash_e_masked_drop_vjp_bwd(h, scale, causal, rate, res, do):
    *core, kv_mask, seed = res
    dqkv = _flash_bwd_e(h, scale, causal, tuple(core), do,
                        kv_mask=kv_mask, drop=rate, seed=seed)
    return (dqkv, jnp.zeros_like(kv_mask),
            np.zeros(jnp.shape(seed), dtype=jax.dtypes.float0))


_flash_e_masked_drop.defvjp(_flash_e_masked_drop_vjp_fwd,
                            _flash_e_masked_drop_vjp_bwd)


def flash_attention_e(qkv: jnp.ndarray,
                      scale: Optional[float] = None,
                      causal: bool = False,
                      kv_mask: Optional[jnp.ndarray] = None,
                      dropout_rate: float = 0.0,
                      dropout_seed=None) -> jnp.ndarray:
    """Self-attention over the projection-native layout: ``qkv``
    (b, s, h, 3*d) — lanes [head][q|k|v] exactly as
    ``proj(x).reshape(b, s, h, 3*d)`` produces — returning the context
    (b, s, h*d) ready for the output projection.  Semantically equal to
    splitting/transposing and calling :func:`flash_attention`, but the
    whole attention boundary carries ZERO relayout copies: inputs are
    lane-blocked views of the projection output, and the backward emits
    one dqkv array in the same layout.

    Eligibility (:func:`flash_e_supported`): 128-aligned-padded
    s <= 1024 runs whole-sequence blocks; longer sequences (up to
    ``APEX_TPU_FLASH_E_MAX_SEQ``, default 32768) stream (bs, bs) tiles
    with online softmax — both keep the zero-relayout property.
    Remaining fallbacks (head/lane-budget misfits, very long s, manual
    shard_map axes) log their reason once and take the transposing
    path.

    ``dropout_rate`` applies attention dropout INSIDE the kernels (the
    reference's fused-MHA in-kernel philox, ref:
    apex/contrib/csrc/multihead_attn/dropout.h): the backward
    regenerates the forward's keep mask from ``dropout_seed`` (an int32
    scalar, traced OK) instead of materializing O(s^2) mask bits.

    ``dropout_seed`` contract: NON-NEGATIVE int32.  The counter hash
    folds the seed through a 31-bit mask (Mosaic-safe uint32 view), so
    a negative seed silently aliases the mask of ``seed & 0x7FFFFFFF``.
    :func:`dropout_seed_from_key` — the canonical derivation — only
    produces non-negative seeds; hand-built seeds must do the same.
    """
    from ._context import in_manual_axis_context
    from .._autocast_ctx import autocast_compute_dtype

    b, s, h, td = qkv.shape
    d = td // 3
    if scale is None:
        scale = d ** -0.5
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    act = autocast_compute_dtype()
    if act is not None and qkv.dtype != act \
            and jnp.issubdtype(qkv.dtype, jnp.floating):
        qkv = qkv.astype(act)
    manual = in_manual_axis_context(qkv)
    mode, why = _e_mode(s, h, d, drop=dropout_rate > 0.0)
    if manual or mode is None:
        reason = "inside shard_map manual axes" if manual else why
        _log_e_fallback(reason, b, s, h, d)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if dropout_rate > 0.0:
            # dropout needs the probabilities, which the reduced flash
            # output no longer carries — the reference path applies the
            # same post-softmax counter-hash mask
            ctx = _fallback_dropout_attention(
                q, k, v, scale, causal, kv_mask, dropout_rate,
                dropout_seed)
        elif manual:
            ctx = mha_reference(q, k, v, scale=scale, causal=causal,
                                kv_mask=kv_mask)
        else:
            ctx = flash_attention(q, k, v, scale=scale, causal=causal,
                                  kv_mask=kv_mask)
        return ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d)
    qkv_e = qkv.reshape(b, s, h * td)
    seed = dropout_seed
    if dropout_rate > 0.0:
        if kv_mask is not None:
            return _flash_e_masked_drop(
                qkv_e, kv_mask.astype(jnp.float32),
                jnp.asarray(seed, jnp.int32), h, scale, causal,
                float(dropout_rate))
        return _flash_e_drop(qkv_e, jnp.asarray(seed, jnp.int32), h,
                             scale, causal, float(dropout_rate))
    if kv_mask is not None:
        return _flash_e_masked(qkv_e, kv_mask.astype(jnp.float32), h,
                               scale, causal)
    return _flash_e_fused(qkv_e, h, scale, causal)


def dropout_seed_from_key(key) -> jnp.ndarray:
    """Derive the int32 ``dropout_seed`` :func:`flash_attention_e`
    expects from a JAX PRNG key — the one canonical mapping, so every
    call site (transformer layers, contrib MHA) stays in sync."""
    return jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)


_E_FALLBACK_SEEN: set = set()


def _log_e_fallback(reason: str, b: int, s: int, h: int, d: int):
    """One line per distinct (shape, reason) per process — the VERDICT
    requirement that silent E-layout fallbacks do not silently re-pay
    the relayout glue."""
    key = (reason, b, s, h, d)
    if key in _E_FALLBACK_SEEN:
        return
    _E_FALLBACK_SEEN.add(key)
    from ..utils.log_util import get_logger

    get_logger(__name__).info(
        "flash_attention_e fallback to transposing path for "
        "(b=%d, s=%d, h=%d, d=%d): %s", b, s, h, d, reason)


def _fallback_dropout_attention(q, k, v, scale, causal, kv_mask, rate,
                                seed):
    """Reference-path attention with the same post-softmax dropout
    semantics as the kernels (counter-hash keep mask; normalization by
    the undropped softmax denominator)."""
    b, h, sq, sk = q.shape[0], q.shape[1], q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, _NEG)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :].astype(bool), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if kv_mask is not None:
        # fully-masked rows: softmax over all-_NEG is uniform garbage;
        # emit exact zeros like the kernels' dead-row guard
        dead = jnp.max(s, axis=-1, keepdims=True) <= _NEG * 0.5
        p = jnp.where(dead, 0.0, p)
    # 4-D counter hash: same fmix32 mixing, element-unique counters
    u32 = functools.partial(jnp.asarray, dtype=jnp.uint32)
    seed_u = jnp.bitwise_and(jnp.asarray(seed, jnp.int32),
                             jnp.int32(0x7FFFFFFF)).astype(jnp.uint32)
    bi = jax.lax.broadcasted_iota(jnp.uint32, p.shape, 0)
    hi = jax.lax.broadcasted_iota(jnp.uint32, p.shape, 1)
    qi = jax.lax.broadcasted_iota(jnp.uint32, p.shape, 2)
    ki = jax.lax.broadcasted_iota(jnp.uint32, p.shape, 3)
    x = (seed_u * u32(0x85EBCA6B) ^ bi * u32(0xC2B2AE35)
         ^ hi * u32(0x27D4EB2F)) + qi * u32(sk) + ki
    x = (x ^ (x >> 16)) * u32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * u32(0xC2B2AE35)
    x = x ^ (x >> 16)
    f = (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    p = jnp.where(f >= jnp.float32(rate), p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def mha_reference(q, k, v, scale=None, causal=False, kv_mask=None,
                  window=None, prefix=0):
    """Unfused reference (the [b,h,sq,sk]-materializing baseline the
    reference's standalone GPT uses) — for parity tests and benchmarks.
    ``kv_mask`` (b, sk): True/nonzero = attend.  ``k``/``v`` of fewer
    heads are repeated for the query heads that read them; ``window``
    (with ``causal``) keeps the ``window`` keys ending at the query;
    ``prefix`` keys stand before the causal square, seen by all."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k.shape[1] != q.shape[1]:
        k, v = (jnp.repeat(x, q.shape[1] // x.shape[1], axis=1)
                for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    sq, sk = s.shape[-2:]
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), prefix)
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((sq, sk), bool), -window)
        s = jnp.where(mask, s, _NEG)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :].astype(bool), s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
