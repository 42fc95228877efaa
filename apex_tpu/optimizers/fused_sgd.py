"""FusedSGD — momentum SGD with a single fused Pallas pass.

Parity with the reference's ``FusedSGD``
(ref: apex/optimizers/fused_sgd.py:4-227): momentum, dampening, nesterov,
``wd_after_momentum``, torch first-step momentum semantics
(buf <- grad).  The reference's ``materialize_master_grads`` fusion of
unscale+copy+step into one kernel (ref: fused_sgd.py:76-95,
apex/amp/_process_optimizer.py:258+) is subsumed here by XLA fusing the
amp unscale into the packed-gradient read.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from ..ops import fused_optim, fused_pipeline, multi_tensor
from .fused_adam import (FusedTransformation, LeafProgram,
                         ScalarOrSchedule, _assemble_model, _clip_enabled,
                         _f32, _gather, _grad_clip_factor, _grad_scale,
                         _lowp_dtype_for, _lr_at, _scatter, _staged_clip,
                         _step_calls, _sumsq_program)


class FusedSGDState(NamedTuple):
    count: jnp.ndarray
    momentum: Tuple[jnp.ndarray, ...]


def fused_sgd(learning_rate: ScalarOrSchedule,
              momentum: float = 0.0,
              dampening: float = 0.0,
              weight_decay: float = 0.0,
              nesterov: bool = False,
              wd_after_momentum: bool = False,
              max_grad_norm=None,
              use_pallas: bool = None) -> "FusedTransformation":
    if nesterov and (momentum <= 0 or dampening != 0):
        raise ValueError(
            "Nesterov momentum requires a momentum and zero dampening "
            "(ref: apex/optimizers/fused_sgd.py:61-62)")

    def init(params):
        metas = multi_tensor.compute_metas(params, split_direct=True)
        return FusedSGDState(
            count=jnp.zeros((), jnp.int32),
            momentum=multi_tensor.state_zeros(metas))

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fused_sgd requires params in update()")
        count = state.count + 1
        lr = _lr_at(learning_rate, count)
        first = (state.count == 0).astype(jnp.float32) if momentum else \
            jnp.float32(0.0)
        metas = multi_tensor.compute_metas(params, split_direct=True)
        gbufs = _staged_clip(multi_tensor.group_buffers(grads, metas),
                             max_grad_norm)
        pbufs = multi_tensor.group_buffers(params, metas)
        deltas, new_mom = [], []
        for i, meta in enumerate(metas):
            if momentum == 0.0:
                # No momentum buffer: plain (optionally decayed) step.
                g = gbufs[i].astype(jnp.float32)
                p32 = pbufs[i].astype(jnp.float32)
                g = g + weight_decay * p32
                deltas.append((-lr * g).astype(meta.dtype))
                new_mom.append(state.momentum[i])
            elif fused_optim.group_use_pallas(use_pallas, meta):
                (gb, pb, mb), restore = fused_optim.flatten_for_kernel(
                    gbufs[i], pbufs[i], state.momentum[i])
                d, mom = fused_optim.sgd_update(
                    gb, pb, mb,
                    lr=lr, momentum=momentum, dampening=dampening,
                    weight_decay=weight_decay, nesterov=nesterov,
                    wd_after_momentum=wd_after_momentum, first_run=first)
                deltas.append(restore(d))
                new_mom.append(restore(mom))
            else:
                d, mom = _sgd_jnp(gbufs[i], pbufs[i], state.momentum[i],
                                  lr, momentum, dampening, weight_decay,
                                  nesterov, wd_after_momentum, first)
                deltas.append(d)
                new_mom.append(mom)
        leaves = jax.tree_util.tree_leaves(params)
        updates = multi_tensor.assemble(
            deltas, metas, out_dtypes=[l.dtype for l in leaves])
        return updates, FusedSGDState(count, tuple(new_mom))

    def sgd_leaf_step(g, p, mom, scalars, *, lowp_dtype):
        lr, first, gscale = (scalars[i] for i in range(3))
        g = g.astype(jnp.float32) * gscale
        if momentum == 0.0:
            p32 = p.astype(jnp.float32)
            p2 = (p32 - lr * (g + weight_decay * p32)).astype(p.dtype)
            mom2 = mom
        else:
            d, mom2 = _sgd_jnp(g, p, mom, lr, momentum, dampening,
                               weight_decay, nesterov, wd_after_momentum,
                               first)
            p2 = p + d
        return p2, mom2, (None if lowp_dtype is None
                          else p2.astype(lowp_dtype))

    leaf_step = LeafProgram(sgd_leaf_step, static_argnames=("lowp_dtype",))
    sumsq = _sumsq_program()

    def fused_step(grads, state, params, model_params=None,
                   grad_scale=None):
        """Single-pass step (+ optional model copy) — the in-place
        ``FusedSGD.step()`` analogue; see FusedTransformation.  Each
        group (every leaf, by default) is one call of the leaf program,
        the vectors one call together (``_step_calls``)."""
        count = state.count + 1
        lr = _lr_at(learning_rate, count)
        first = (state.count == 0).astype(jnp.float32) if momentum else \
            jnp.float32(0.0)
        metas = multi_tensor.compute_metas(params, split_direct=True)
        gbufs = multi_tensor.group_buffers(grads, metas)
        pbufs = multi_tensor.group_buffers(params, metas)
        model_leaves = (jax.tree_util.tree_leaves(model_params)
                        if model_params is not None else None)
        lowp_dts = [_lowp_dtype_for(meta, pbufs[i], model_leaves)
                    for i, meta in enumerate(metas)]
        calls = _step_calls(metas, pbufs, lowp_dts)
        gs = [_gather(c, gbufs) for c in calls]
        gscale = _grad_scale(gs, grad_scale, max_grad_norm, sumsq)
        scalars = _f32(lr, first, gscale)
        new_p, new_mom, lowps = ([None] * len(metas) for _ in range(3))
        for c, g in zip(calls, gs):
            p, mom = (_gather(c, b) for b in (pbufs, state.momentum))
            lowp_dt = lowp_dts[c.groups[0]]
            if momentum != 0.0 and fused_optim.step_use_pallas(
                    use_pallas, sum(sum(metas[i].sizes) for i in c.groups)):
                flats, restore = fused_optim.flatten_for_kernel(
                    g.astype(jnp.float32) * gscale, p, mom)
                outs = fused_optim.sgd_step(
                    *flats, lr=lr, momentum=momentum,
                    dampening=dampening, weight_decay=weight_decay,
                    nesterov=nesterov,
                    wd_after_momentum=wd_after_momentum,
                    first_run=first, lowp_dtype=lowp_dt)
                p2, mom2 = restore(outs[0]), restore(outs[1])
                lp = restore(outs[2]) if lowp_dt is not None else None
            else:
                p2, mom2, lp = leaf_step(g, p, mom, scalars,
                                         lowp_dtype=lowp_dt)
            for into, out in ((new_p, p2), (new_mom, mom2), (lowps, lp)):
                _scatter(c, out, into)
        leaves = jax.tree_util.tree_leaves(params)
        new_params = multi_tensor.assemble(
            new_p, metas, out_dtypes=[l.dtype for l in leaves])
        model_out = None
        if model_leaves is not None:
            model_out = _assemble_model(new_p, lowps, metas,
                                        model_leaves)
        return new_params, FusedSGDState(count, tuple(new_mom)), \
            model_out

    def pipeline_init(metas):
        """Persistent packed momentum buffers (fp32 per group)."""
        return FusedSGDState(
            count=jnp.zeros((), jnp.int32),
            momentum=tuple(jnp.zeros((m.padded,), jnp.float32)
                           for m in metas))

    def pipeline_step(gbufs, state, master_bufs, metas, *,
                      grad_scale=1.0, grad_norm=None, finite=True):
        """The clip+SGD+cast sweep over the persistent packed buffers —
        see fused_adam's pipeline_step for the skip/count semantics."""
        finite = jnp.asarray(finite)
        count = state.count + finite.astype(jnp.int32)
        lr = _lr_at(learning_rate, state.count + 1)
        first = (state.count == 0).astype(jnp.float32) if momentum else \
            jnp.float32(0.0)
        gscale = jnp.asarray(grad_scale, jnp.float32)
        if _clip_enabled(max_grad_norm):
            if grad_norm is None:
                # see fused_adam.pipeline_step: static-scaling amp
                # elided the norm sweep; derive it only for the clip
                grad_norm = fused_pipeline.packed_norm(gbufs, gscale)
            gscale = gscale * _grad_clip_factor(grad_norm, max_grad_norm)
        new_p, new_mom, lowps = [], [], []
        for i, meta in enumerate(metas):
            lowp_dt = fused_pipeline.group_lowp_dtype(meta)
            if momentum == 0.0:
                p = master_bufs[i]
                g32 = gbufs[i].astype(jnp.float32) * gscale
                p2 = jnp.where(finite,
                               p - lr * (g32 + weight_decay * p), p)
                mom2, lp = state.momentum[i], None
            else:
                p2, mom2, lp = fused_pipeline.sgd_pipeline(
                    gbufs[i], master_bufs[i], state.momentum[i],
                    grad_scale=gscale, lr=lr, momentum=momentum,
                    dampening=dampening, weight_decay=weight_decay,
                    nesterov=nesterov,
                    wd_after_momentum=wd_after_momentum,
                    first_run=first, finite=finite,
                    lowp_dtype=lowp_dt, use_pallas=use_pallas)
            if lp is None:
                lp = p2.astype(lowp_dt) if lowp_dt is not None else p2
            new_p.append(p2)
            new_mom.append(mom2)
            lowps.append(lp)
        return (tuple(new_p), FusedSGDState(count, tuple(new_mom)),
                lowps)

    return FusedTransformation(init, update, fused_step,
                               pipeline_init, pipeline_step,
                               (leaf_step, sumsq))


def _sgd_jnp(g, p, mom, lr, momentum, dampening, wd, nesterov,
             wd_after_momentum, first_run):
    g = g.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    if not wd_after_momentum:
        g = g + wd * p32
    mom = jnp.where(first_run > 0.5, g,
                    momentum * mom + (1.0 - dampening) * g)
    upd = g + momentum * mom if nesterov else mom
    if wd_after_momentum:
        upd = upd + wd * p32
    return (-lr * upd).astype(p.dtype), mom


FusedSGD = fused_sgd
