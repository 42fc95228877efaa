"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context capability the reference does NOT have (its fused softmax
caps at seq 2048 and FMHA at 512, ref: fused_softmax.py:151-170,
setup.py:408-424; SURVEY §2.10 records SP/CP as absent).  Here sequence
length becomes a *scaling axis*: Q, K, V are sharded over a mesh axis,
K/V blocks rotate around the ring with one ``ppermute`` per step, and
each device merges blockwise-attention partials with the online-softmax
(max, sumexp, accumulator) recurrence — attention memory per chip is
O(s_local^2) and the K/V hops ride ICI neighbour links (Liu et al. 2023,
"Ring Attention with Blockwise Transformers"; merge math is the flash
attention combine).

Call inside ``shard_map`` with q, k, v sequence-sharded on
``axis_name``; the result is the bit-for-tolerance equivalent of dense
softmax attention over the full sequence.

Head dim 64 (the reference FMHA's native size): the flash mode's
per-shard partials automatically ride the head-packed d=64 kernels —
two heads per 128-lane MXU tile via the sigma rotation (see the
head-packing note in :mod:`.flash_attention`) — whenever ``h`` is even,
roughly doubling per-shard MXU throughput over the old half-width path
(escape hatch: ``APEX_TPU_FLASH_PACK_D64=0`` /
``flash_attention.set_head_packing(False)``).  The dropout keep masks
are coordinate-hashed in GLOBAL positions, so packed and unpacked
shards draw identical masks and the ring merge is unaffected.
"""
from __future__ import annotations

from typing import Optional

import jax
from .._compat import axis_index, axis_size, typeof
import jax.numpy as jnp

_NEG = -1e30


def flash_legal_here(*operands) -> bool:
    """True when a Pallas call on these operands is legal in the current
    trace context — i.e. the enclosing ``shard_map`` runs with
    ``check_vma=False`` (no operand carries a varying-mesh-axis type).
    Under ``check_vma=True`` sequence-sharded operands are vma-typed and
    pallas_call is rejected by JAX, so the einsum path must run.

    This is what lets ``use_flash=None`` (the default) pick the fast
    kernel automatically: probed on the CPU mesh, a ``P('sp')`` operand
    shows ``vma={'sp'}`` under ``check_vma=True`` and ``vma=set()``
    under ``check_vma=False``."""
    for x in operands:
        try:
            vma = getattr(typeof(x), "vma", None)
        except (AttributeError, TypeError):
            return False  # operand untypable
        if vma is None or vma:
            return False
    return True


def _block_attend(q, k, v, scale, qpos, kpos, causal, drop=0.0,
                  seed=None, q_off=0, k_off=0, head_off=0):
    """One blockwise partial: returns (m, l, acc) for local q against
    this k/v block, with causal masking by GLOBAL positions.  ``drop``
    applies the coordinate-hash keep mask (bit-identical to the flash
    kernels' — :func:`..flash_attention.rand_keep_global` at global
    offsets ``q_off``/``k_off``/``head_off``) to the VALUE accumulation
    only; ``l`` stays undropped so the cross-block merge normalizes by
    the true softmax denominator, exactly like dense in-kernel
    dropout."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = kpos[None, :] <= qpos[:, None]          # True = attend
        s = jnp.where(mask[None, None], s, _NEG)
    m = jnp.max(s, axis=-1)                            # (b, h, sq)
    p = jnp.exp(s - m[..., None])
    # fully-masked rows: m = _NEG -> p rows would be exp(0)=1; zero them
    p = jnp.where((m > _NEG / 2)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    pa = p
    if drop > 0.0:
        from .flash_attention import rand_keep_global

        keep = rand_keep_global(s.shape, seed, drop, q_offset=q_off,
                                k_offset=k_off, head_offset=head_off)
        pa = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - drop))
    acc = jnp.einsum("bhqk,bhkd->bhqd", pa.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str,
                   scale: Optional[float] = None,
                   causal: bool = False,
                   use_flash: Optional[bool] = None,
                   dropout_rate: float = 0.0,
                   dropout_seed=None) -> jnp.ndarray:
    """Exact attention with K/V rotating around ``axis_name``.

    Shapes (per shard): q, k, v are (b, h, s_local, d); the global
    sequence is ``axis_size * s_local`` with shard i owning positions
    ``[i*s_local, (i+1)*s_local)``.  Returns the local output shard
    (b, h, s_local, d).

    ``use_flash=None`` (default) picks automatically: the Pallas flash
    partial runs whenever the enclosing ``shard_map`` legality allows
    it (``check_vma=False`` — detected via :func:`flash_legal_here`),
    else the einsum path.  ``use_flash=True`` asserts the flash path
    (errors loudly under ``check_vma=True``); ``False`` forces einsum.

    The flash mode computes each block with
    :func:`..flash_attention.flash_attention_partial` and merges
    (o, lse) pairs — per-step attention memory drops from the
    materialized O(s_local^2) fp32 scores to the kernel's blockwise
    working set, and the MXU kernel replaces the unfused einsum
    softmax.  At d=64 with even ``h`` the partial runs the head-packed
    full-width kernels (module note above).  Same math either way;
    causal blocks wholly in the future still run their (masked)
    matmuls in both modes — the merge annihilates them.

    ``dropout_rate`` applies attention dropout with GLOBAL-position
    keep masks (the round-4 in-kernel dropout, threaded through SP):
    shard r draws rows [r*s_local, ...) and rotated-block columns of
    ONE global mask — bit-identical in both modes and equal to a dense
    evaluation of :func:`..flash_attention.rand_keep_global` — so
    long-context SP training configs get the same dropout semantics as
    the single-chip kernels.  ``dropout_seed``: non-negative int32
    (see :func:`..flash_attention.dropout_seed_from_key`).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if use_flash is None:
        use_flash = flash_legal_here(q, k, v)
    nshards = axis_size(axis_name)
    rank = axis_index(axis_name)
    s_local = q.shape[-2]
    perm = [(i, (i + 1) % nshards) for i in range(nshards)]
    drop_kw = (dict(dropout_rate=dropout_rate,
                    dropout_seed=dropout_seed)
               if dropout_rate > 0.0 else {})

    if use_flash:
        from .flash_attention import flash_attention_partial

        qoff = rank * s_local

        def fstep(carry, i):
            kk, vv, o, lse = carry
            kk = jax.lax.ppermute(kk, axis_name, perm)
            vv = jax.lax.ppermute(vv, axis_name, perm)
            src = (rank - i) % nshards
            bo, blse = flash_attention_partial(
                q, kk, vv, scale=scale, causal=causal,
                q_offset=qoff, k_offset=src * s_local, **drop_kw)
            lse_new = jnp.logaddexp(lse, blse)
            o = (o * jnp.exp(lse - lse_new)[..., None]
                 + bo.astype(o.dtype) * jnp.exp(blse - lse_new)[..., None])
            return (kk, vv, o, lse_new), None

        o0, lse0 = flash_attention_partial(
            q, k, v, scale=scale, causal=causal,
            q_offset=qoff, k_offset=qoff, **drop_kw)
        if nshards > 1:
            (_, _, o, _), _ = jax.lax.scan(
                fstep, (k, v, o0.astype(jnp.float32), lse0),
                jnp.arange(1, nshards))
        else:
            o = o0
        return o.astype(q.dtype)

    qpos = rank * s_local + jnp.arange(s_local)

    def merge(m, l, acc, bm, bl, bacc):
        m_new = jnp.maximum(m, bm)
        c_old = jnp.exp(m - m_new)
        c_blk = jnp.exp(bm - m_new)
        # guard: rows never touched keep m = _NEG; exp(_NEG-_NEG)=1 ok
        l = l * c_old + bl * c_blk
        acc = acc * c_old[..., None] + bacc * c_blk[..., None]
        return m_new, l, acc

    def step(carry, i):
        kk, vv, m, l, acc = carry
        # Rotate FIRST (steps 1..n-1): after i rotations the held block
        # originated at rank - i, and no trailing hop is wasted (the
        # final iteration's rotation would otherwise be discarded — one
        # superfluous pair of ICI collectives per layer per step).
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        src = (rank - i) % nshards
        kpos = src * s_local + jnp.arange(s_local)
        bm, bl, bacc = _block_attend(q, kk, vv, scale, qpos, kpos,
                                     causal, drop=dropout_rate,
                                     seed=dropout_seed,
                                     q_off=rank * s_local,
                                     k_off=src * s_local)
        m, l, acc = merge(m, l, acc, bm, bl, bacc)
        return (kk, vv, m, l, acc), None

    # step 0: the local block, no hop
    m0, l0, acc0 = _block_attend(q, k, v, scale, qpos, qpos, causal,
                                 drop=dropout_rate, seed=dropout_seed,
                                 q_off=rank * s_local,
                                 k_off=rank * s_local)
    if nshards > 1:
        (_, _, m, l, acc), _ = jax.lax.scan(
            step, (k, v, m0, l0, acc0), jnp.arange(1, nshards))
    else:
        m, l, acc = m0, l0, acc0
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str,
                      scale: Optional[float] = None,
                      causal: bool = False,
                      attention_fn=None,
                      use_flash: Optional[bool] = None,
                      dropout_rate: float = 0.0,
                      dropout_seed=None) -> jnp.ndarray:
    """DeepSpeed-Ulysses style sequence parallelism: all-to-all swaps
    the sharded axis from SEQUENCE to HEADS, runs full-sequence
    attention locally on a head subset, and swaps back.

    Per-shard shapes (b, h, s_local, d) with ``h %% axis_size == 0``.
    Two all-to-alls replace the ring's ``axis_size`` ppermutes —
    preferable when heads are plentiful and ICI all-to-all bandwidth is
    good; ring attention wins when s_local is large enough to overlap
    compute with the hops.

    ``use_flash=None`` (default) runs the real Pallas kernel for the
    local full-sequence attention whenever the enclosing ``shard_map``
    legality allows it (``check_vma=False``, via
    :func:`flash_legal_here`); under ``check_vma=True`` the local core
    is ``flash_attention``'s XLA reference fallback.  ``True`` asserts
    the kernel, ``False`` forces the fallback core.

    ``dropout_rate``: attention dropout with the SAME global
    coordinate-hash mask as :func:`ring_attention` — here the shard
    owns a HEAD subset of the full sequence, so the mask window is
    selected by ``head_offset = rank * h_local`` instead of sequence
    offsets.  A fixed seed draws identical global masks in ring and
    Ulysses mode.
    """
    nshards = axis_size(axis_name)
    b, h, s_local, d = q.shape
    assert h % nshards == 0, (
        f"heads {h} not divisible by axis size {nshards}")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if use_flash is None:
        use_flash = flash_legal_here(q, k, v)

    def seq_to_heads(x):
        # (b, h, s_local, d) -> (b, h/P, P*s_local, d)
        x = jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                               tiled=True)
        return x

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    head_off = axis_index(axis_name) * (h // nshards)
    if attention_fn is None:
        if use_flash:
            # bypass flash_attention's manual-axis fallback: the Pallas
            # call is legal under shard_map(check_vma=False)
            from .flash_attention import flash_attention_partial

            def attention_fn(q, k, v, scale=None, causal=False):
                kw = (dict(dropout_rate=dropout_rate,
                           dropout_seed=dropout_seed,
                           head_offset=head_off)
                      if dropout_rate > 0.0 else {})
                return flash_attention_partial(q, k, v, scale=scale,
                                               causal=causal, **kw)[0]
        elif dropout_rate > 0.0:
            # einsum core with the same global coordinate-hash mask
            # (the check_vma=True context, e.g. the CPU-mesh dryrun);
            # head_off selects this shard's window of the global mask.
            # Reuses the ring path's _block_attend (whole sequence as
            # one block) so the attention/dropout math lives once.
            def attention_fn(q, k, v, scale=None, causal=False):
                if scale is None:
                    scale = q.shape[-1] ** -0.5
                pos = jnp.arange(q.shape[-2])
                _, l, acc = _block_attend(
                    q, k, v, scale, pos, pos, causal,
                    drop=dropout_rate, seed=dropout_seed,
                    head_off=head_off)
                out = acc / jnp.maximum(l, 1e-30)[..., None]
                return out.astype(q.dtype)
        else:
            from .flash_attention import flash_attention as attention_fn
    out = attention_fn(qh, kh, vh, scale=scale, causal=causal)
    return heads_to_seq(out)
