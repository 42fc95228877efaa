"""FusedLAMB — layerwise adaptive large-batch optimizer.

Parity with the reference's two-phase ``FusedLAMB``
(ref: apex/optimizers/fused_lamb.py:1-215): phase 1 computes the global
grad norm (``multi_tensor_l2norm``) and the Adam-style update
(``multi_tensor_lamb`` stage 1, csrc/multi_tensor_lamb.cu:60-200); phase 2
applies per-tensor trust ratios (stage 2, :230-330).  Options:
``bias_correction``, ``grad_averaging``, ``adam_w_mode``,
``max_grad_norm``, ``use_nvlamb``.

TPU design: params/grads/state are packed into LANE-aligned flat fp32
buffers per dtype group; stage 1 is one fused Pallas pass (4 reads /
3 writes); per-tensor param/update norms are segment reductions over the
packed buffer (the reference's per-tensor-norm kernel role); stage 2's
ratio gather+multiply is left to XLA, which fuses it into a single
elementwise pass — on TPU there is no launch overhead for a Pallas
kernel to amortize there.

Trust-ratio gating matches the reference exactly: the adaptive ratio is
applied only when ``use_nvlamb`` or the group's weight decay is nonzero
(ref: csrc/multi_tensor_lamb.cu:258 ``use_nvlamb || decay != 0.0``).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from ..ops import fused_optim, fused_pipeline, multi_tensor
from .fused_adam import (FusedTransformation, LeafProgram,
                         ScalarOrSchedule, _assemble_model, _f32, _gather,
                         _grad_clip_factor, _grad_scale, _lowp_dtype_for,
                         _lr_at, _scatter, _step_calls, _sumsq_program)


class FusedLAMBState(NamedTuple):
    count: jnp.ndarray
    # fp32 per group: flat (padded,) buffer for packed groups, native
    # leaf shape for DIRECT groups (split_direct metas)
    m: Tuple[jnp.ndarray, ...]
    v: Tuple[jnp.ndarray, ...]


def fused_lamb(learning_rate: ScalarOrSchedule = 1e-3,
               beta1: float = 0.9,
               beta2: float = 0.999,
               eps: float = 1e-6,
               weight_decay: float = 0.01,
               bias_correction: bool = True,
               grad_averaging: bool = True,
               adam_w_mode: bool = True,
               max_grad_norm: float = 1.0,
               use_nvlamb: bool = False,
               use_pallas: bool = None) -> "FusedTransformation":
    if eps <= 0.0:
        # Packed trust-ratio math needs phase-1 to map zero-filled
        # alignment gaps to exactly 0 (per_tensor_sumsq folds each gap
        # into the preceding tensor's norm); eps=0 makes gaps 0/0=NaN
        # and silently poisons that tensor's ratio.
        raise ValueError("fused_lamb requires eps > 0 "
                         "(packed padding-gap invariant)")
    LANE = multi_tensor.LANE
    beta3 = (1.0 - beta1) if grad_averaging else 1.0

    def _bias_corrections(count):
        cf = count.astype(jnp.float32)
        if bias_correction:
            return (1.0 - jnp.float32(beta1) ** cf,
                    1.0 - jnp.float32(beta2) ** cf)
        return jnp.float32(1.0), jnp.float32(1.0)

    def init(params):
        metas = multi_tensor.compute_metas(params, align=LANE,
                                           split_direct=True)
        zeros = multi_tensor.state_zeros(metas)
        return FusedLAMBState(count=jnp.zeros((), jnp.int32),
                              m=zeros,
                              v=tuple(jnp.zeros_like(z) for z in zeros))

    def _deltas(grads, state, params):
        """Shared LAMB math -> (metas, pbufs, group deltas, new state).
        Grads may arrive in low precision (fused path): the packed /
        phase-1 math upcasts per group."""
        count = state.count + 1
        lr = _lr_at(learning_rate, count)
        bc1, bc2 = _bias_corrections(count)

        metas = multi_tensor.compute_metas(params, align=LANE,
                                           split_direct=True)
        gbufs = multi_tensor.group_buffers(grads, metas)
        pbufs = multi_tensor.group_buffers(params, metas)

        # Phase 1a: global grad norm + clip factor over ALL groups
        # (ref: apex/optimizers/fused_lamb.py:163-185 multi_tensor_l2norm
        # over the union of fp16+fp32 grads; padding gaps are zero).
        gnorm, clip = _global_grad_clip(gbufs, max_grad_norm)

        deltas, new_m, new_v = [], [], []
        for i, meta in enumerate(metas):
            adapted_u, m, v = _lamb_group_update(
                meta, gbufs[i], pbufs[i], state.m[i], state.v[i],
                gscale=clip, beta1=beta1, beta2=beta2, beta3=beta3,
                eps=eps, weight_decay=weight_decay, bc1=bc1, bc2=bc2,
                adam_w_mode=adam_w_mode, use_nvlamb=use_nvlamb,
                fused=fused_optim.group_use_pallas(use_pallas, meta))
            deltas.append(-lr * adapted_u)
            new_m.append(m)
            new_v.append(v)
        new_state = FusedLAMBState(count, tuple(new_m), tuple(new_v))
        return metas, pbufs, deltas, new_state

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fused_lamb requires params in update()")
        metas, _, deltas, new_state = _deltas(grads, state, params)
        leaves = jax.tree_util.tree_leaves(params)
        updates = multi_tensor.assemble(
            deltas, metas, out_dtypes=[l.dtype for l in leaves])
        return updates, new_state

    def lamb_leaf_step(g, p, m, v, scalars, *, meta, fused, lowp_dtype):
        """Phase 1, the trust ratio (a direct leaf's two norms, or the
        per-tensor norms of a packed group's ``meta``) and the apply."""
        lr, bc1, bc2, gscale = (scalars[i] for i in range(4))
        adapted_u, m2, v2 = _lamb_group_update(
            meta, g, p, m, v, gscale=gscale, beta1=beta1, beta2=beta2,
            beta3=beta3, eps=eps, weight_decay=weight_decay, bc1=bc1,
            bc2=bc2, adam_w_mode=adam_w_mode, use_nvlamb=use_nvlamb,
            fused=fused)
        p2 = (p.astype(jnp.float32) + -lr * adapted_u).astype(p.dtype)
        return p2, m2, v2, (None if lowp_dtype is None
                            else p2.astype(lowp_dtype))

    leaf_step = LeafProgram(lamb_leaf_step,
                            static_argnames=("meta", "fused", "lowp_dtype"))
    sumsq = _sumsq_program()

    def fused_step(grads, state, params, model_params=None,
                   grad_scale=None):
        """Single-pass step (+ optional model copy) — see
        FusedTransformation: the global norm is a sum of the sumsq
        program's outputs, then each group (every leaf, by default) is
        one call of the leaf program, the vectors one call together
        (``_step_calls``), whose update, apply and master->model
        writeback share one fusion scope."""
        if params is None:
            raise ValueError("fused_lamb requires params")
        count = state.count + 1
        lr = _lr_at(learning_rate, count)
        bc1, bc2 = _bias_corrections(count)
        metas = multi_tensor.compute_metas(params, align=LANE,
                                           split_direct=True)
        gbufs = multi_tensor.group_buffers(grads, metas)
        pbufs = multi_tensor.group_buffers(params, metas)
        model_leaves = (jax.tree_util.tree_leaves(model_params)
                        if model_params is not None else None)
        lowp_dts = [_lowp_dtype_for(meta, pbufs[i], model_leaves)
                    for i, meta in enumerate(metas)]
        # a bundle's vectors start on a lane row each, so its per-tensor
        # trust-ratio norms are row-aligned slices
        calls = _step_calls(metas, pbufs, lowp_dts, align=LANE)
        gs = [_gather(c, gbufs) for c in calls]
        gscale = _grad_scale(gs, grad_scale, max_grad_norm, sumsq)
        scalars = _f32(lr, bc1, bc2, gscale)
        new_p, new_m, new_v, lowps = ([None] * len(metas)
                                      for _ in range(4))
        for c, g in zip(calls, gs):
            first = metas[c.groups[0]]
            meta = first if c.meta is None else c.meta
            p2, m2, v2, lp = leaf_step(
                g, *(_gather(c, b) for b in (pbufs, state.m, state.v)),
                scalars,
                # a direct leaf's program must not key on its position
                meta=None if multi_tensor.is_direct(meta) else meta,
                # a bundle dispatches as its direct groups would
                fused=fused_optim.group_use_pallas(use_pallas, first),
                lowp_dtype=lowp_dts[c.groups[0]])
            for into, out in ((new_p, p2), (new_m, m2), (new_v, v2),
                              (lowps, lp)):
                _scatter(c, out, into)
        leaves = jax.tree_util.tree_leaves(params)
        new_params = multi_tensor.assemble(
            new_p, metas, out_dtypes=[l.dtype for l in leaves])
        new_state = FusedLAMBState(count, tuple(new_m), tuple(new_v))
        model_out = None
        if model_leaves is not None:
            model_out = _assemble_model(new_p, lowps, metas,
                                        model_leaves)
        return new_params, new_state, model_out

    def pipeline_init(metas):
        """Persistent packed m/v (fp32 per group); the pipeline layout
        is LANE-aligned by construction, so the per-tensor trust-ratio
        reductions stay row-friendly."""
        zeros = tuple(jnp.zeros((m.padded,), jnp.float32) for m in metas)
        return FusedLAMBState(count=jnp.zeros((), jnp.int32), m=zeros,
                              v=tuple(jnp.zeros_like(z) for z in zeros))

    def pipeline_step(gbufs, state, master_bufs, metas, *,
                      grad_scale=1.0, grad_norm=None, finite=True):
        """LAMB over the persistent packed buffers: the global grad
        norm arrives pre-computed from the pipeline's fused norm sweep
        (``grad_norm`` — the unscaled norm, so ``max_grad_norm`` keeps
        its staged meaning); clip and amp unscale fold into phase 1's
        ``gscale``; the trust-ratio stage reuses the exact
        ``_lamb_group_update`` machinery of the staged path."""
        if grad_norm is None:
            # static-scaling amp elides the norm/finite sweep; LAMB's
            # clip always needs the unscaled norm, so derive it here
            # (one fused read — the same cost the staged path pays)
            grad_norm = fused_pipeline.packed_norm(gbufs, grad_scale)
        finite = jnp.asarray(finite)
        count = state.count + finite.astype(jnp.int32)
        stepped = state.count + 1
        lr = _lr_at(learning_rate, stepped)
        bc1, bc2 = _bias_corrections(stepped)
        gscale = jnp.asarray(grad_scale, jnp.float32) \
            * _grad_clip_factor(grad_norm, max_grad_norm)
        fused = fused_pipeline.use_pallas_pipeline(use_pallas)
        new_p, new_m, new_v, lowps = [], [], [], []
        for i, meta in enumerate(metas):
            adapted_u, m2, v2 = _lamb_group_update(
                meta, gbufs[i], master_bufs[i], state.m[i], state.v[i],
                gscale=gscale, beta1=beta1, beta2=beta2, beta3=beta3,
                eps=eps, weight_decay=weight_decay, bc1=bc1, bc2=bc2,
                adam_w_mode=adam_w_mode, use_nvlamb=use_nvlamb,
                fused=fused)
            p2 = jnp.where(finite, master_bufs[i] - lr * adapted_u,
                           master_bufs[i])
            lowp_dt = fused_pipeline.group_lowp_dtype(meta)
            new_p.append(p2)
            new_m.append(jnp.where(finite, m2, state.m[i]))
            new_v.append(jnp.where(finite, v2, state.v[i]))
            lowps.append(p2.astype(lowp_dt) if lowp_dt is not None
                         else p2)
        return (tuple(new_p),
                FusedLAMBState(count, tuple(new_m), tuple(new_v)),
                lowps)

    return FusedTransformation(init, update, fused_step,
                               pipeline_init, pipeline_step,
                               (leaf_step, sumsq))


def _global_grad_clip(gbufs, max_norm):
    """Global grad norm over all packed groups + clip factor
    (ref: apex/optimizers/fused_lamb.py:163-185).  ``max_norm`` None/0
    disables clipping.  Mixed-precision LAMB passes
    ``max_grad_norm * loss_scale`` because its norm is of scaled grads
    (ref: fused_mixed_precision_lamb.py:182-184).

    Norm structure note (measured, BERT-large step): the per-leaf
    reduces below cost ~10.6 ms/step in the UNROLLED step (~400 small
    fusions x ~25 us dispatch + forced fp32 grad materialization) and a
    per-dtype concatenated variant won ~2 ms there — but inside the
    shipping ``lax.scan`` training form the concat REGRESSED the step
    134 -> 144 ms (the scan body re-copies the concat buffer every
    iteration).  Per-leaf is the better shipping form; see
    ROUND3_NOTES "LAMB step anatomy"."""
    gsq = sum(multi_tensor.sumsq(g) for g in gbufs)
    gnorm = jnp.sqrt(gsq)
    # The enable decision must be static (max_norm may be a traced value
    # when the caller scales it by a traced loss scale — pass None to
    # disable in that case); _grad_clip_factor makes it so.
    return gnorm, _grad_clip_factor(gnorm, max_norm)


def _lamb_group_update(meta, gbuf, pbuf, m, v, *, gscale, beta1, beta2,
                       beta3, eps, weight_decay, bc1, bc2, adam_w_mode,
                       use_nvlamb, fused):
    """Stage 1 (Pallas or jnp) + per-tensor trust ratio for one packed
    dtype group.  Returns ``(ratio*update, m_new, v_new)``; the caller
    applies the learning rate (and any overflow select).  Shared by
    FusedLAMB and FusedMixedPrecisionLamb so the clip/trust-ratio
    semantics can never diverge between them."""
    if fused:
        (gb, pb, mb, vb), restore = fused_optim.flatten_for_kernel(
            gbuf, pbuf, m, v)
        u, m_new, v_new = fused_optim.lamb_phase1(
            gb, pb, mb, vb, grad_scale=gscale, beta1=beta1, beta2=beta2,
            beta3=beta3, eps=eps, weight_decay=weight_decay,
            bias_correction1=bc1, bias_correction2=bc2,
            adam_w_mode=adam_w_mode)
        u, m_new, v_new = restore(u), restore(m_new), restore(v_new)
    else:
        u, m_new, v_new = _lamb_phase1_jnp(
            gbuf, pbuf, m, v, gscale, beta1, beta2, beta3, eps,
            weight_decay, bc1, bc2, adam_w_mode)
    ratio_elem = _trust_ratio_elem(meta, u, pbuf.astype(jnp.float32),
                                   use_nvlamb, weight_decay)
    return ratio_elem * u, m_new, v_new


def _trust_ratio_elem(meta, u, p32, use_nvlamb, weight_decay):
    """Phase 2 ratios: per-tensor param/update norms broadcast back per
    element (ref: multi_tensor_lamb.cu:230-330 LAMBStage2; per-tensor
    norms are the l2norm kernel's per_tensor=True output).  Packed
    groups use static-slice reductions — no segment ops, whose
    packed-length index arrays explode program size at BERT-large scale
    (see multi_tensor.per_tensor_sumsq).

    DIRECT groups (one native-shape leaf; ``meta`` None in a leaf
    program) reduce over the whole buffer — one scalar ratio, no
    packing."""
    if meta is None or multi_tensor.is_direct(meta):
        if use_nvlamb or weight_decay != 0.0:
            p_n2 = jnp.sum(p32 * p32)
            u_n2 = jnp.sum(u.astype(jnp.float32) ** 2)
            return jnp.where(
                (p_n2 > 0) & (u_n2 > 0),
                jnp.sqrt(p_n2) / jnp.sqrt(jnp.maximum(u_n2, 1e-24)),
                1.0)
        return jnp.float32(1.0)
    if not (use_nvlamb or weight_decay != 0.0):
        # ref: multi_tensor_lamb.cu:258 — plain LAMB leaves zero-decay
        # params un-adapted.
        return jnp.float32(1.0)
    p_nsq = multi_tensor.per_tensor_sumsq(p32, meta)
    u_nsq = multi_tensor.per_tensor_sumsq(u, meta)
    ratio = jnp.where((p_nsq > 0) & (u_nsq > 0),
                      jnp.sqrt(p_nsq) / jnp.sqrt(
                          jnp.maximum(u_nsq, 1e-24)), 1.0)
    return multi_tensor.broadcast_per_tensor(ratio, meta)


def _lamb_phase1_jnp(g, p, m, v, gscale, b1, b2, b3, eps, wd, bc1, bc2,
                     adam_w_mode):
    """Stage-1 math in plain jnp (ref: csrc/multi_tensor_lamb.cu:60-200)."""
    g = g.astype(jnp.float32) * gscale
    p32 = p.astype(jnp.float32)
    if not adam_w_mode:
        g = g + wd * p32
    m_new = b1 * m + b3 * g
    v_new = b2 * v + (1.0 - b2) * g * g
    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if adam_w_mode:
        u = u + wd * p32
    return u, m_new, v_new


FusedLAMB = fused_lamb
