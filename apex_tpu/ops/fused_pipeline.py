"""Persistent packed optimizer pipeline: the whole post-backward step in
two HBM sweeps.

The reference's core perf feature is ``multi_tensor_apply`` — fused
kernels that stream many small tensors per launch (ref:
apex/optimizers/fused_adam.py:147-170, csrc/multi_tensor_l2norm /
_scale / _adam).  On TPU the equivalent economics is HBM traffic, and
the measured reason the earlier packed path lost (0.60-0.73x vs direct,
see ops/multi_tensor.py's DIRECT_MIN_ELEMS log) was *re-packing every
step*: pack/unpack of params+state cost more memory traffic than the
fusion saved.  This module removes the per-step repack instead of the
packing:

* **Persistent packing** — fp32 masters and optimizer state live in
  LANE-aligned packed flat buffers *across* steps
  (:class:`PackedMasters` + the optimizers' ``pipeline_init``), donated
  buffer-for-buffer through the jitted train step.  Only gradients are
  packed per step (:func:`pack_grads`), via per-leaf
  ``dynamic_update_slice`` writes into a zero-initialized flat buffer —
  static offsets, so XLA fuses the writes with the gradient producers.
  The only per-step unpack is the master->model-dtype cast the update
  sweep already emits (``multi_tensor.assemble`` of the ``lowp``
  outputs).

* **Sweep 1** (:func:`grad_norm_finite`) — one read-only pass over the
  packed grad buffers fusing amp unscale, the overflow finite-check,
  and the global-L2-norm partials (the reference's
  ``multi_tensor_l2norm`` + ``multi_tensor_scale`` overflow-buffer
  roles).  Nothing grad-sized is written: the unscale itself is folded
  into sweep 2's combined scale factor.

* **Sweep 2** (:func:`adam_pipeline` / :func:`sgd_pipeline`, LAMB via
  its shared phase-1/trust-ratio machinery) — one read-modify-write
  pass fusing clip-scale, the optimizer update, the overflow skip-select
  and the master->model cast (the ``multi_tensor_adam`` role).  The
  skip is a ``where``-select inside the same sweep, so overflow steps
  cost no extra pass and no ``lax.cond`` double-compilation.

Each sweep has a Pallas kernel and a pure-jnp twin with identical math.
Auto dispatch (``use_pallas=None``) resolves to the jnp twin: measured
on v5e, XLA's fused elementwise loops reach ~880 GB/s where a
hand-rolled Pallas elementwise stream reached ~190 GB/s
(ops/fused_optim.py ``step_use_pallas`` log) — the pipeline's win is
the persistent layout plus expression adjacency, not the kernel
authorship.  ``APEX_TPU_PIPELINE_PALLAS=1`` (or ``use_pallas=True``)
routes both sweeps through the Pallas kernels for hardware where the
trade-off shifts; tools/ci.sh runs them in interpret mode on CPU every
run (:func:`self_check`).

``APEX_TPU_FUSED_PIPELINE=0`` disables the pipeline wholesale —
:class:`apex_tpu.amp.AmpOptimizer` then keeps the per-stage path
(unscale pass, finite pass, ``fused_step``, master->model convert).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis.flags import flag_bool
from . import fused_optim, multi_tensor
from .multi_tensor import LANE, FlatMeta

# Force every leaf into chunked per-dtype packs (no direct groups):
# persistent buffers amortize the pack across the whole run, so the
# per-step packing loss DIRECT_MIN_ELEMS guards against does not apply.
_ALL_PACKED = 1 << 62


def pipeline_enabled(flag: Optional[bool] = None) -> bool:
    """Resolve the pipeline on/off switch: an explicit flag wins, else
    the ``APEX_TPU_FUSED_PIPELINE`` env var (default ON; ``0`` is the
    escape hatch back to the per-stage path).  Read per call so setting
    the var after import still takes effect for new optimizers."""
    if flag is not None:
        return bool(flag)
    return flag_bool("APEX_TPU_FUSED_PIPELINE")


def use_pallas_pipeline(flag: Optional[bool] = None) -> bool:
    """Kernel dispatch for the two pipeline sweeps.  Explicit flag wins;
    auto resolves to the jnp twins (see module docstring for the
    measured rationale) unless ``APEX_TPU_PIPELINE_PALLAS=1``."""
    if flag is not None:
        return bool(flag)
    return flag_bool("APEX_TPU_PIPELINE_PALLAS")


def pipeline_metas(tree: Any) -> List[FlatMeta]:
    """Packing layout for the persistent pipeline: LANE-aligned offsets
    (row-friendly per-tensor reductions for LAMB), every leaf packed,
    chunked at ``PACK_MAX_ELEMS`` (the XLA pair-layout temp guard).
    Group key is the leaf dtype — compute the metas from the MODEL
    (cast) tree so gradient buffers group identically; masters pack
    into the same layout with ``dtype=float32``."""
    return multi_tensor.compute_metas(tree, align=LANE, split_direct=True,
                                      direct_min=_ALL_PACKED)


def packed_nbytes(tree: Any) -> int:
    """Pre-alignment byte total of ``tree`` in its own leaf dtypes
    (shapes/dtypes only — safe on arrays, tracers, and
    ``ShapeDtypeStruct`` templates).  The quantity the
    ``APEX_TPU_PIPELINE_PACK_MIN_BYTES`` routing cutoff compares: the
    persistent pipeline's win is amortizing the pack across a run, and
    below a packed-size floor the measured 0.73x small-tree residue
    says direct per-leaf updates are the faster regime."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            arr = jnp.asarray(leaf)
            shape, dtype = arr.shape, arr.dtype
        size = 1
        for d in shape:
            size *= int(d)
        total += size * jnp.dtype(dtype).itemsize
    return total


def pack_grads(tree: Any, metas: Sequence[FlatMeta]) -> List[jnp.ndarray]:
    """Pack a gradient pytree into flat buffers by per-leaf
    ``dynamic_update_slice`` writes into a zero-initialized buffer.

    This replaces the concatenate-based :func:`multi_tensor.pack` on
    the per-step path: offsets are static Python ints, so each write
    lowers to a fusible in-place update-slice — XLA can emit the
    gradient producer's output directly into the flat buffer instead of
    materializing the leaf then gathering it (the copy chain behind the
    measured 0.60-0.73x packed_vs_direct loss).  Alignment gaps and the
    tail stay exactly zero (the LAMB ``per_tensor_sumsq`` gap
    invariant).

    Each group's buffer dtype is the widest dtype among its member
    gradients (at least the group's model dtype): a user feeding fp32
    accumulated gradients against an fp16/bf16 model must never have
    them silently downcast — under a 2^16 loss scale an fp32->fp16
    cast would overflow to inf BEFORE the unscale sweep (the staged
    path accepts any grad dtype; so does the pipeline)."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    out = []
    for meta in metas:
        dt = jnp.result_type(meta.dtype,
                             *(jnp.asarray(leaves[i]).dtype
                               for i in meta.leaf_indices))
        buf = jnp.zeros((meta.padded,), dt)
        for k, i in enumerate(meta.leaf_indices):
            piece = jnp.ravel(jnp.asarray(leaves[i])).astype(dt)
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, piece, meta.offsets[k], axis=0)
        out.append(buf)
    return out


@dataclasses.dataclass(frozen=True)
class PackedMasters:
    """fp32 master weights as persistent packed flat buffers.

    A pytree whose leaves are the per-group buffers and whose aux data
    is the static packing layout — it checkpoints, donates, and
    ``tree_map``s like any other master tree while never being
    unpacked.  The model-dtype view is produced by the update sweep
    (``lowp`` outputs); :meth:`to_model` exists for the cold paths
    (checkpoint restore, debugging) that need params without a step.
    """

    bufs: Tuple[jnp.ndarray, ...]
    metas: Tuple[FlatMeta, ...]

    def to_model(self, template: Any) -> Any:
        """Assemble the model-dtype param pytree from the packed
        masters.  ``template`` may hold abstract leaves
        (``ShapeDtypeStruct``) — only dtypes are read; the tree
        structure comes from the packing metas."""
        leaves = jax.tree_util.tree_leaves(template)
        dtypes = [getattr(l, "dtype", None) or jnp.asarray(l).dtype
                  for l in leaves]
        return multi_tensor.assemble(list(self.bufs), list(self.metas),
                                     out_dtypes=dtypes)


jax.tree_util.register_pytree_node(
    PackedMasters,
    lambda pm: (pm.bufs, pm.metas),
    lambda metas, bufs: PackedMasters(tuple(bufs), metas),
)


def _pm_to_state_dict(pm: PackedMasters) -> dict:
    from flax import serialization

    return {"bufs": serialization.to_state_dict(list(pm.bufs))}


def _pm_from_state_dict(pm: PackedMasters, state: dict) -> PackedMasters:
    from flax import serialization

    bufs = serialization.from_state_dict(list(pm.bufs), state["bufs"])
    return PackedMasters(tuple(bufs), pm.metas)


try:
    # flax msgpack checkpointing (examples/imagenet/main_amp.py) needs
    # an explicit handler for custom pytree nodes: the buffers
    # serialize, the static layout comes from the restore target.
    from flax import serialization as _flax_serialization

    _flax_serialization.register_serialization_state(
        PackedMasters, _pm_to_state_dict, _pm_from_state_dict)
except ImportError:  # flax-less deployments still get the pipeline
    pass


def pack_masters(params: Any, model_template: Any) -> PackedMasters:
    """Build the persistent packed master state: layout from the MODEL
    (cast) tree — so per-step gradient packing groups identically —
    buffers snapshotted fp32 from the original (highest-precision)
    ``params``, exactly as the reference clones masters before the
    low-precision cast (ref: apex/amp/_process_optimizer.py:28-44)."""
    metas = pipeline_metas(model_template)
    bufs = tuple(multi_tensor.pack(params, [m], jnp.float32)[0]
                 for m in metas)
    return PackedMasters(bufs, tuple(metas))


# --------------------------------------------------------------------------
# Sweep 1: unscale + finite-check + global-norm partials (read-only)
# --------------------------------------------------------------------------

def _norm_finite_kernel(total_rows: int, block_rows: int, hyp_ref,
                        g_ref, part_ref, fin_ref):
    """Per-block partial sum-of-squares of (g * inv_scale) plus a
    finite flag; partials land in per-block SMEM slots (no
    cross-iteration accumulation) and are reduced outside.  The ragged
    last block is masked by row index — the buffer's own zero padding
    needs no mask (zeros contribute nothing and are finite)."""
    i = pl.program_id(0)
    g = g_ref[:].astype(jnp.float32) * hyp_ref[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) \
        + i * block_rows
    g = jnp.where(rows < total_rows, g, 0.0)
    part_ref[i] = jnp.sum(g * g)
    fin_ref[i] = jnp.all(jnp.isfinite(g)).astype(jnp.int32)


def _norm_finite_pallas(buf: jnp.ndarray, inv: jnp.ndarray,
                        interpret=None):
    n = buf.shape[0]
    assert n % LANE == 0, f"flat buffer length {n} not a multiple of {LANE}"
    rows = n // LANE
    block_rows = min(fused_optim.BLOCK_ROWS, rows)
    grid = -(-rows // block_rows)
    view = buf.reshape(rows, LANE)
    kernel = functools.partial(_norm_finite_kernel, rows, block_rows)
    parts, fins = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block_rows, LANE), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        # whole (grid,) arrays in SMEM, one slot per program: the TPU
        # lowering refuses a rank-1 block of 1
        out_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 2,
        out_shape=[jax.ShapeDtypeStruct((grid,), jnp.float32),
                   jax.ShapeDtypeStruct((grid,), jnp.int32)],
        interpret=fused_optim._interpret() if interpret is None
        else interpret,
    )(inv.reshape(1), view)
    return jnp.sum(parts), jnp.all(fins > 0)


def _norm_finite_jnp(buf: jnp.ndarray, inv: jnp.ndarray):
    """jnp twin of :func:`_norm_finite_pallas` — one buffer's
    (sum-of-squares, finite) partial for the norm/finite sweep."""
    g = buf.astype(jnp.float32) * inv
    return multi_tensor.sumsq(g), jnp.all(jnp.isfinite(g))


def grad_norm_finite(gbufs: Sequence[jnp.ndarray], inv_scale=1.0,
                     use_pallas: Optional[bool] = None, interpret=None):
    """ONE read-only sweep over the packed grad buffers ->
    ``(global_norm, finite)`` of the *unscaled* gradients
    (``g * inv_scale`` in fp32) — the fused
    ``multi_tensor_l2norm`` + overflow-buffer stage of the pipeline.
    The unscaled values are never written: callers fold ``inv_scale``
    into the update sweep's combined scale instead."""
    inv = jnp.asarray(inv_scale, jnp.float32)
    sums, fins = [], []
    for buf in gbufs:
        if use_pallas_pipeline(use_pallas):
            s, f = _norm_finite_pallas(buf, inv, interpret=interpret)
        else:
            s, f = _norm_finite_jnp(buf, inv)
        sums.append(s)
        fins.append(f)
    if not sums:
        return jnp.float32(0.0), jnp.bool_(True)
    total = sums[0]
    for s in sums[1:]:
        total = total + s
    return jnp.sqrt(total), jnp.stack(fins).all()


def packed_norm(gbufs: Sequence[jnp.ndarray], scale=1.0) -> jnp.ndarray:
    """Global L2 norm of ``g * scale`` over packed buffers — the
    norm-only form for callers that already know the grads are finite
    (or don't care): optimizer-level clipping when amp elided the
    norm/finite sweep under static scaling."""
    if not gbufs:
        return jnp.float32(0.0)
    s = jnp.asarray(scale, jnp.float32)
    total = None
    for buf in gbufs:
        part = multi_tensor.sumsq(buf.astype(jnp.float32) * s)
        total = part if total is None else total + part
    return jnp.sqrt(total)


# --------------------------------------------------------------------------
# Sweep 2: clip-scale + update + skip-select + master->model cast
# --------------------------------------------------------------------------

def _adam_pipeline_kernel(adam_w_mode: bool, emit_lowp: bool, hyp_ref,
                          g_ref, p_ref, m_ref, v_ref, *out_refs):
    if emit_lowp:
        p_out, m_out, v_out, lowp_ref = out_refs
    else:
        p_out, m_out, v_out = out_refs
    lr, b1, b2, eps, wd, bc1, bc2, gscale, keep = (hyp_ref[i]
                                                   for i in range(9))
    g = g_ref[:].astype(jnp.float32) * gscale
    p = p_ref[:]
    m_old = m_ref[:]
    v_old = v_ref[:]
    if not adam_w_mode:
        # ADAM_MODE_0: L2 decay folds into the gradient
        # (ref: multi_tensor_adam.cu:60-78).
        g = g + wd * p
    m = b1 * m_old + (1.0 - b1) * g
    v = b2 * v_old + (1.0 - b2) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w_mode:
        upd = upd + wd * p
    ok = keep > 0.5
    p_new = jnp.where(ok, p - lr * upd, p)
    p_out[:] = p_new
    m_out[:] = jnp.where(ok, m, m_old)
    v_out[:] = jnp.where(ok, v, v_old)
    if emit_lowp:
        lowp_ref[:] = p_new.astype(lowp_ref.dtype)


def _adam_pipeline_jnp(g, p, m, v, lr, b1, b2, eps, wd, bc1, bc2,
                       gscale, finite, adam_w_mode, lowp_dtype):
    g = g.astype(jnp.float32) * gscale
    if not adam_w_mode:
        g = g + wd * p
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    if adam_w_mode:
        upd = upd + wd * p
    p_new = jnp.where(finite, p - lr * upd, p)
    m_new = jnp.where(finite, m_new, m)
    v_new = jnp.where(finite, v_new, v)
    lowp = p_new.astype(lowp_dtype) if lowp_dtype is not None else None
    return p_new, m_new, v_new, lowp


def adam_pipeline(g, p, m, v, *, grad_scale, lr, beta1, beta2, eps,
                  weight_decay, bias_correction1, bias_correction2,
                  adam_w_mode=True, finite=True, lowp_dtype=None,
                  use_pallas: Optional[bool] = None, interpret=None):
    """The Adam update sweep over one packed group: combined-scale the
    grads (unscale x clip, pre-folded into ``grad_scale``), Adam/AdamW
    update, overflow skip-select (``finite``), and the master->model
    cast (``lowp_dtype``) — one read of g/p/m/v, one write of
    p/m/v[/lowp].  Returns ``(new_p, new_m, new_v, lowp_or_None)``."""
    finite = jnp.asarray(finite)
    if not use_pallas_pipeline(use_pallas):
        return _adam_pipeline_jnp(
            g, p, m, v, lr, beta1, beta2, eps, weight_decay,
            bias_correction1, bias_correction2, grad_scale, finite,
            adam_w_mode, lowp_dtype)
    hyp = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.float32(beta1),
        jnp.float32(beta2), jnp.float32(eps), jnp.float32(weight_decay),
        jnp.asarray(bias_correction1, jnp.float32),
        jnp.asarray(bias_correction2, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
        finite.astype(jnp.float32)])
    out_dtypes = [jnp.float32, jnp.float32, jnp.float32]
    if lowp_dtype is not None:
        out_dtypes.append(lowp_dtype)
    kernel = functools.partial(_adam_pipeline_kernel, adam_w_mode,
                               lowp_dtype is not None)
    outs = fused_optim._elementwise_call(kernel, hyp, [g, p, m, v],
                                         out_dtypes, interpret=interpret)
    if lowp_dtype is None:
        return outs[0], outs[1], outs[2], None
    return outs[0], outs[1], outs[2], outs[3]


def _sgd_pipeline_kernel(nesterov: bool, wd_after_momentum: bool,
                         emit_lowp: bool, hyp_ref, g_ref, p_ref,
                         mom_ref, *out_refs):
    if emit_lowp:
        p_out, mom_out, lowp_ref = out_refs
    else:
        p_out, mom_out = out_refs
    lr, momentum, dampening, wd, first_run, gscale, keep = (
        hyp_ref[i] for i in range(7))
    g = g_ref[:].astype(jnp.float32) * gscale
    p = p_ref[:]
    mom_old = mom_ref[:]
    if not wd_after_momentum:
        g = g + wd * p
    mom = jnp.where(first_run > 0.5, g,
                    momentum * mom_old + (1.0 - dampening) * g)
    upd = g + momentum * mom if nesterov else mom
    if wd_after_momentum:
        upd = upd + wd * p
    ok = keep > 0.5
    p_new = jnp.where(ok, p - lr * upd, p)
    p_out[:] = p_new
    mom_out[:] = jnp.where(ok, mom, mom_old)
    if emit_lowp:
        lowp_ref[:] = p_new.astype(lowp_ref.dtype)


def _sgd_pipeline_jnp(g, p, mom, lr, momentum, dampening, wd,
                      first_run, gscale, finite, nesterov,
                      wd_after_momentum, lowp_dtype):
    g = g.astype(jnp.float32) * gscale
    if not wd_after_momentum:
        g = g + wd * p
    mom_new = jnp.where(first_run > 0.5, g,
                        momentum * mom + (1.0 - dampening) * g)
    upd = g + momentum * mom_new if nesterov else mom_new
    if wd_after_momentum:
        upd = upd + wd * p
    p_new = jnp.where(finite, p - lr * upd, p)
    mom_new = jnp.where(finite, mom_new, mom)
    lowp = p_new.astype(lowp_dtype) if lowp_dtype is not None else None
    return p_new, mom_new, lowp


def sgd_pipeline(g, p, mom, *, grad_scale, lr, momentum, dampening,
                 weight_decay, nesterov=False, wd_after_momentum=False,
                 first_run, finite=True, lowp_dtype=None,
                 use_pallas: Optional[bool] = None, interpret=None):
    """The momentum-SGD update sweep over one packed group — see
    :func:`adam_pipeline`.  Returns ``(new_p, new_mom, lowp_or_None)``."""
    finite = jnp.asarray(finite)
    if not use_pallas_pipeline(use_pallas):
        return _sgd_pipeline_jnp(
            g, p, mom, lr, momentum, dampening, weight_decay,
            jnp.asarray(first_run, jnp.float32), grad_scale, finite,
            nesterov, wd_after_momentum, lowp_dtype)
    hyp = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.float32(momentum),
        jnp.float32(dampening), jnp.float32(weight_decay),
        jnp.asarray(first_run, jnp.float32),
        jnp.asarray(grad_scale, jnp.float32),
        finite.astype(jnp.float32)])
    out_dtypes = [jnp.float32, jnp.float32]
    if lowp_dtype is not None:
        out_dtypes.append(lowp_dtype)
    kernel = functools.partial(_sgd_pipeline_kernel, nesterov,
                               wd_after_momentum, lowp_dtype is not None)
    outs = fused_optim._elementwise_call(kernel, hyp, [g, p, mom],
                                         out_dtypes, interpret=interpret)
    if lowp_dtype is None:
        return outs[0], outs[1], None
    return outs[0], outs[1], outs[2]


def group_lowp_dtype(meta: FlatMeta):
    """The update sweep's model-copy output dtype for one group: the
    group's (model) dtype, or None when the model group is already fp32
    (the master buffer itself is the model copy then)."""
    return None if jnp.dtype(meta.dtype) == jnp.dtype(jnp.float32) \
        else meta.dtype


# --------------------------------------------------------------------------
# CI self-check: Pallas interpret-mode kernels vs staged path on CPU
# --------------------------------------------------------------------------

def self_check(steps: int = 3) -> None:
    """Kernel-regression guard run by tools/ci.sh on every CI pass (no
    TPU needed): drives the full amp pipeline with the Pallas sweeps
    FORCED (interpret mode on CPU) for ``steps`` steps on a tiny
    mixed-dtype tree and asserts parity against the per-stage path —
    masters, model params, and optimizer state."""
    import numpy as np

    from .. import amp
    from ..optimizers import fused_adam

    params = {
        "w": jnp.linspace(-1.0, 1.0, 96, dtype=jnp.float32).reshape(8, 12),
        "b": jnp.linspace(0.1, 0.5, 7, dtype=jnp.float32),
        "deep": {"k": jnp.full((5, 3), 0.25, jnp.float32)},
    }
    grads = jax.tree_util.tree_map(lambda x: 0.01 * x + 0.003, params)
    policy = amp.get_policy("O5", loss_scale=256.0)

    def run(pipeline, use_pallas):
        tx = fused_adam(1e-2, weight_decay=0.01, max_grad_norm=0.5,
                        use_pallas=use_pallas)
        opt = amp.AmpOptimizer(tx, policy, check_finite=True,
                               pipeline=pipeline)
        state = opt.init(params)
        model = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
        for i in range(steps):
            g = jax.tree_util.tree_map(
                lambda x: (x * (1.0 + 0.1 * i)
                           * policy.effective_loss_scale
                           ).astype(jnp.bfloat16), grads)
            model, state, info = opt.apply_gradients(g, state, model)
        return model, state, info

    model_k, state_k, info_k = run(pipeline=True, use_pallas=True)
    model_s, state_s, _ = run(pipeline=False, use_pallas=False)
    masters_k = state_k.master_params.to_model(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params))
    # rtol covers the clip factor's reduction-order ulps (packed-buffer
    # norm vs the staged path's per-group norm); the unclipped update
    # math itself is bitwise (tests/test_fused_pipeline.py proves that)
    for a, b in zip(jax.tree_util.tree_leaves(masters_k),
                    jax.tree_util.tree_leaves(state_s.master_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(model_k),
                    jax.tree_util.tree_leaves(model_s)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-2, atol=1e-2)
    assert info_k.grad_norm is not None and bool(
        jnp.isfinite(info_k.grad_norm))
    # the norm/finite sweep agrees between Pallas (interpret) and jnp
    gb = pack_grads(jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), grads),
        pipeline_metas(model_k))
    n_p, f_p = grad_norm_finite(gb, 0.5, use_pallas=True)
    n_j, f_j = grad_norm_finite(gb, 0.5, use_pallas=False)
    np.testing.assert_allclose(float(n_p), float(n_j), rtol=1e-6)
    assert bool(f_p) and bool(f_j)
    print(f"[fused_pipeline] self-check OK: {steps} steps, Pallas "
          f"interpret sweeps == staged path (grad_norm "
          f"{float(info_k.grad_norm):.4f})")


if __name__ == "__main__":
    self_check()
