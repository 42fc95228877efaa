"""FusedAdam — Adam/AdamW with a single fused Pallas pass.

Capability parity with the reference's ``FusedAdam``
(ref: apex/optimizers/fused_adam.py:4-173): ``adam_w_mode`` switching
Adam-L2 vs AdamW, ``bias_correction``, bf16/fp16/fp32 params
(ref: fused_adam.py:134 bf16 support), one fused kernel launch per dtype
group (ref: fused_adam.py:147-170 multi_tensor_applier calls).

Exposed as an optax-compatible ``GradientTransformation``: update deltas
come back in param dtype; ``m``/``v`` state lives in packed fp32 flat
buffers so the Pallas kernel streams params+grads+state in one pass
(see apex_tpu/ops/fused_optim.py).  Set ``use_pallas=False`` for the
per-leaf pure-jnp path (identical math; XLA-fused per leaf).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
import optax

from ..ops import fused_optim, fused_pipeline, multi_tensor

ScalarOrSchedule = Union[float, jnp.ndarray, Callable]


class FusedAdamState(NamedTuple):
    count: jnp.ndarray
    m: Tuple[jnp.ndarray, ...]   # fp32 flat buffer per dtype group
    v: Tuple[jnp.ndarray, ...]


class FusedTransformation(NamedTuple):
    """optax-compatible transformation with an extra single-pass
    ``fused_step``: ``(new_params, new_state, model_params) =
    fused_step(grads, state, params, model_params=None)``.

    ``update`` keeps the optax delta protocol; ``fused_step`` is the
    in-place analogue of the reference's ``FusedAdam.step()`` — it
    applies the update AND (given ``model_params``, the low-precision
    template under amp master weights) emits the cast model copy from
    the same kernel pass, saving the delta round-trip and the separate
    master->model convert.

    ``fused_step``'s ``grad_scale`` (None = 1) is a factor every
    gradient is multiplied by in fp32 before the update: amp passes its
    inverse loss scale here instead of unscaling the gradient tree, and
    any clip factor joins it.  The per-leaf math runs in
    ``leaf_programs`` (:class:`LeafProgram`), one jitted program per
    distinct signature: a call a matrix leaf, and one for the vectors
    together (``_step_calls``).

    ``pipeline_init`` / ``pipeline_step`` (None when the optimizer has
    no pipeline form) are the persistent-packed entry points used by
    :class:`apex_tpu.amp.AmpOptimizer` when asked to pack
    (``pipeline=True``, see ops/fused_pipeline.py): state lives in
    packed flat fp32 buffers across steps, and ``pipeline_step(gbufs,
    state, master_bufs, metas, grad_scale=..., grad_norm=...,
    finite=...)`` performs the whole clip+update+cast sweep over them,
    returning ``(new_master_bufs, new_state, lowp_bufs)``."""
    init: Any
    update: Any
    fused_step: Any
    pipeline_init: Any = None
    pipeline_step: Any = None
    leaf_programs: Tuple["LeafProgram", ...] = ()


class LeafProgram:
    """One ``jax.jit`` program of a per-leaf optimizer step, called once
    a matrix leaf and once for a step's vectors (``_step_calls``).  JAX
    traces a nested jit once a signature and lowers each distinct callee
    once a module, so a train step holds one function per distinct
    (shape, dtype) signature and a ``call`` per call site: its tracing
    grows with the signatures (about a dozen in a GPT-2 or BERT tree),
    not with the ~300 leaves.  The TPU compiler inlines the calls
    before fusion, so the device program is the one the inlined math
    would give.  Traced scalars come in as one float32
    vector (``_f32``: a weak-typed Python float would split the cache);
    hyperparameters are closed over, and ``static`` keywords are static
    arguments.

    ``signatures`` holds every signature called with: the programs
    this optimizer has traced (``AmpOptimizer.leaf_programs``)."""

    def __init__(self, fn, static_argnames=()):
        self._jit = jax.jit(fn, static_argnames=static_argnames)
        self.signatures = set()

    def __call__(self, *arrays, **static):
        self.signatures.add(
            (tuple((tuple(a.shape), jnp.dtype(a.dtype)) for a in arrays),
             tuple(sorted(static.items()))))
        return self._jit(*arrays, **static)


def _f32(*xs):
    """A leaf program's scalars as ONE float32 vector, made once a step:
    a constant passed on its own (a fixed learning rate) would be
    re-emitted beside every call."""
    return jnp.stack([jnp.asarray(x, jnp.float32) for x in xs])


def _sumsq_program() -> LeafProgram:
    """The one-output leaf program of a global gradient norm: a leaf's
    fp32 sum of squares after the factor ``scale``."""
    def leaf_sumsq(g, scale):
        return multi_tensor.sumsq(g.astype(jnp.float32) * scale)

    return LeafProgram(leaf_sumsq)


def _lr_at(lr: ScalarOrSchedule, count):
    return lr(count) if callable(lr) else lr


def _clip_enabled(max_norm) -> bool:
    """Static clip on/off: None or a non-positive Python number disables
    (a traced max_norm is always enabled — the caller opted in)."""
    return not (max_norm is None or (isinstance(max_norm, (int, float))
                                     and max_norm <= 0))


def _grad_clip_factor(gnorm, max_norm):
    """``min(1, max_norm/gnorm)`` in the reference's guarded form
    (ref: apex/optimizers/fused_lamb.py:163-185 clipped global norm) —
    the single shared clip-factor expression, so the staged and
    pipeline paths can never diverge on clip semantics."""
    if not _clip_enabled(max_norm):
        return jnp.float32(1.0)
    return jnp.where(gnorm > max_norm,
                     max_norm / jnp.maximum(gnorm, 1e-12), 1.0)


def _grad_scale(gbufs, grad_scale, max_norm, sumsq):
    """The fp32 factor the leaf programs multiply the gradients
    ``gbufs`` by: amp's inverse loss scale (``grad_scale``; 1 without
    amp) times, with a clip configured, the clip factor of the unscaled
    global norm, whose sums of squares come from the ``sumsq`` leaf
    program, one call a buffer."""
    scale = jnp.asarray(1.0 if grad_scale is None else grad_scale,
                        jnp.float32)
    if not _clip_enabled(max_norm):
        return scale
    gnorm = jnp.sqrt(sum(sumsq(b, scale) for b in gbufs))
    return scale * _grad_clip_factor(gnorm, max_norm)


class _Call(NamedTuple):
    """One leaf-program call of a fused step: the groups (positions in
    the step's metas) it steps, and for a bundle of vectors the packed
    layout of their buffers (``meta``; None for one group in its own
    buffer)."""
    groups: Tuple[int, ...]
    meta: Any = None


def _step_calls(metas, pbufs, lowp_dtypes, align=1):
    """The leaf-program calls of a fused step.  Every group is a call of
    its own, except the direct groups of vector leaves (rank 0 or 1:
    biases, norm scales), which share one call a (master dtype, model
    dtype): their buffers pack into one flat buffer a step and the
    results are sliced back.  A vector packs by concatenation, with no
    change of layout, and all of BERT-large's ~200 vectors hold 0.1% of
    its weights, while each leaf stepped alone adds its whole update to
    the program the TPU compiler optimizes and schedules (PERF.md
    section 6, BERT-large's step compiled for a v5e on an 8-core host:
    with every leaf alone 5.9 s more in the compiler's HLO passes than
    the packed step, with the vectors in one call 0.7-2.8 s, and the
    whole compile no longer).  The optimizer state keeps one array a
    leaf."""
    calls, vectors = [], {}
    for i, meta in enumerate(metas):
        if multi_tensor.is_direct(meta) and pbufs[i].ndim <= 1:
            key = (jnp.dtype(pbufs[i].dtype), lowp_dtypes[i])
            vectors.setdefault(key, []).append(i)
        else:
            calls.append(_Call((i,)))
    for groups in vectors.values():
        meta = None
        if len(groups) > 1:
            meta, = multi_tensor.compute_metas(
                [pbufs[i] for i in groups], align=align)
        calls.append(_Call(tuple(groups), meta))
    return calls


def _gather(call, bufs):
    """A call's operand: its group's own buffer, or its bundle packed."""
    if call.meta is None:
        return bufs[call.groups[0]]
    return multi_tensor.pack([bufs[i] for i in call.groups],
                             [call.meta])[0]


def _scatter(call, buf, into):
    """A call's result ``buf`` back into ``into``'s per-group slots, a
    bundle's sliced back to each leaf's shape (None stays None)."""
    if call.meta is None or buf is None:
        for i in call.groups:
            into[i] = buf
        return
    for i, piece in zip(call.groups,
                        multi_tensor.unpack_groups([buf], [call.meta])):
        into[i] = piece


def _staged_clip(gbufs, max_norm):
    """Grad-clip for the optax ``update`` paths: global norm over the
    group buffers (fp32), buffers pre-scaled by the clip factor.
    ``fused_step`` and the pipeline fold the same factor into their
    combined scale instead of materializing scaled grads."""
    if not _clip_enabled(max_norm):
        return gbufs
    gnorm = jnp.sqrt(sum(multi_tensor.sumsq(b) for b in gbufs))
    clip = _grad_clip_factor(gnorm, max_norm)
    return [b.astype(jnp.float32) * clip for b in gbufs]


def _lowp_dtype_for(meta, pbuf, model_leaves):
    """Model-copy dtype for a DIRECT group when it differs from the
    master dtype (packed groups cast via assemble instead)."""
    if model_leaves is None or not multi_tensor.is_direct(meta):
        return None
    mdt = model_leaves[meta.leaf_indices[0]].dtype
    return mdt if mdt != jnp.dtype(pbuf.dtype) else None


def _assemble_model(new_p, lowps, metas, model_leaves):
    return multi_tensor.assemble(
        [lp if lp is not None else p2 for lp, p2 in zip(lowps, new_p)],
        metas, out_dtypes=[l.dtype for l in model_leaves])


def fused_adam(learning_rate: ScalarOrSchedule = 1e-3,
               beta1: float = 0.9,
               beta2: float = 0.999,
               eps: float = 1e-8,
               weight_decay: float = 0.0,
               adam_w_mode: bool = True,
               bias_correction: bool = True,
               max_grad_norm=None,
               use_pallas: bool = None) -> "FusedTransformation":
    """Build the FusedAdam transformation (ref: apex/optimizers/fused_adam.py:4).

    ``max_grad_norm`` (None = off) enables global-norm gradient
    clipping before the update, matching FusedLAMB's clipped-global-
    grad-norm semantics; in pipeline mode the clip factor comes from
    the fused norm sweep and folds into the update kernel's combined
    scale (no extra pass)."""

    def _bias_corrections(count):
        cf = count.astype(jnp.float32)
        if bias_correction:
            return (1.0 - jnp.float32(beta1) ** cf,
                    1.0 - jnp.float32(beta2) ** cf)
        return jnp.float32(1.0), jnp.float32(1.0)

    def init(params):
        metas = multi_tensor.compute_metas(params, split_direct=True)
        zeros = multi_tensor.state_zeros(metas)
        return FusedAdamState(count=jnp.zeros((), jnp.int32),
                              m=zeros, v=tuple(jnp.zeros_like(z)
                                               for z in zeros))

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fused_adam requires params in update()")
        count = state.count + 1
        lr = _lr_at(learning_rate, count)
        bc1, bc2 = _bias_corrections(count)

        metas = multi_tensor.compute_metas(params, split_direct=True)
        gbufs = _staged_clip(multi_tensor.group_buffers(grads, metas),
                             max_grad_norm)
        pbufs = multi_tensor.group_buffers(params, metas)
        deltas, new_m, new_v = [], [], []
        for i, meta in enumerate(metas):
            if fused_optim.group_use_pallas(use_pallas, meta):
                (gb, pb, mb, vb), restore = fused_optim.flatten_for_kernel(
                    gbufs[i], pbufs[i], state.m[i], state.v[i])
                d, m, v = fused_optim.adam_update(
                    gb, pb, mb, vb,
                    lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                    weight_decay=weight_decay,
                    bias_correction1=bc1, bias_correction2=bc2,
                    adam_w_mode=adam_w_mode)
                d, m, v = restore(d), restore(m), restore(v)
            else:
                d, m, v = _adam_jnp(
                    gbufs[i], pbufs[i], state.m[i], state.v[i],
                    lr, beta1, beta2, eps, weight_decay, bc1, bc2,
                    adam_w_mode)
            deltas.append(d)
            new_m.append(m)
            new_v.append(v)
        leaves = jax.tree_util.tree_leaves(params)
        updates = multi_tensor.assemble(
            deltas, metas, out_dtypes=[l.dtype for l in leaves])
        return updates, FusedAdamState(count, tuple(new_m), tuple(new_v))

    def adam_leaf_step(g, p, m, v, scalars, *, lowp_dtype):
        lr, bc1, bc2, gscale = (scalars[i] for i in range(4))
        d, m2, v2 = _adam_jnp(g.astype(jnp.float32) * gscale, p, m, v,
                              lr, beta1, beta2, eps, weight_decay, bc1,
                              bc2, adam_w_mode)
        p2 = p + d
        return p2, m2, v2, (None if lowp_dtype is None
                            else p2.astype(lowp_dtype))

    leaf_step = LeafProgram(adam_leaf_step, static_argnames=("lowp_dtype",))
    sumsq = _sumsq_program()

    def fused_step(grads, state, params, model_params=None,
                   grad_scale=None):
        """Single-pass step: new params (+ optional model copy) without
        the optax delta round-trip — see FusedTransformation.  Each
        group (every leaf, by default) is one call of the leaf program,
        the vectors one call together (``_step_calls``)."""
        count = state.count + 1
        lr = _lr_at(learning_rate, count)
        bc1, bc2 = _bias_corrections(count)

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
        scalars = _f32(lr, bc1, bc2, gscale)
        new_p, new_m, new_v, lowps = ([None] * len(metas)
                                      for _ in range(4))
        for c, g in zip(calls, gs):
            p, m, v = (_gather(c, b) for b in (pbufs, state.m, state.v))
            lowp_dt = lowp_dts[c.groups[0]]
            if fused_optim.step_use_pallas(
                    use_pallas, sum(sum(metas[i].sizes) for i in c.groups)):
                flats, restore = fused_optim.flatten_for_kernel(
                    g.astype(jnp.float32) * gscale, p, m, v)
                outs = fused_optim.adam_step(
                    *flats, lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                    weight_decay=weight_decay, bias_correction1=bc1,
                    bias_correction2=bc2, adam_w_mode=adam_w_mode,
                    lowp_dtype=lowp_dt)
                p2, m2, v2 = (restore(o) for o in outs[:3])
                lp = restore(outs[3]) if lowp_dt is not None else None
            else:
                p2, m2, v2, lp = leaf_step(g, p, m, v, scalars,
                                           lowp_dtype=lowp_dt)
            for into, out in ((new_p, p2), (new_m, m2), (new_v, v2),
                              (lowps, lp)):
                _scatter(c, out, into)
        leaves = jax.tree_util.tree_leaves(params)
        new_params = multi_tensor.assemble(
            new_p, metas, out_dtypes=[l.dtype for l in leaves])
        new_state = FusedAdamState(count, tuple(new_m), tuple(new_v))
        model_out = None
        if model_leaves is not None:
            model_out = _assemble_model(new_p, lowps, metas,
                                        model_leaves)
        return new_params, new_state, model_out

    def pipeline_init(metas):
        """Optimizer state in the persistent packed layout (one fp32
        flat buffer per pipeline group) — see FusedTransformation."""
        zeros = tuple(jnp.zeros((m.padded,), jnp.float32) for m in metas)
        return FusedAdamState(count=jnp.zeros((), jnp.int32), m=zeros,
                              v=tuple(jnp.zeros_like(z) for z in zeros))

    def pipeline_step(gbufs, state, master_bufs, metas, *,
                      grad_scale=1.0, grad_norm=None, finite=True):
        """The clip+Adam+cast sweep over the persistent packed buffers.

        ``grad_scale`` is the amp inverse loss scale (combined with any
        caller-side factor); ``grad_norm`` the unscaled global norm
        from the fused norm sweep (required when ``max_grad_norm`` is
        set); ``finite`` the overflow flag — non-finite steps return
        state bitwise unchanged via an in-sweep select, matching the
        staged path's ``lax.cond`` skip (count held still too)."""
        finite = jnp.asarray(finite)
        count = state.count + finite.astype(jnp.int32)
        lr = _lr_at(learning_rate, state.count + 1)
        bc1, bc2 = _bias_corrections(state.count + 1)
        gscale = jnp.asarray(grad_scale, jnp.float32)
        if _clip_enabled(max_grad_norm):
            if grad_norm is None:
                # amp elided the norm/finite sweep (static scaling):
                # derive the unscaled norm here — one fused read, only
                # paid when clipping is actually configured
                grad_norm = fused_pipeline.packed_norm(gbufs, gscale)
            gscale = gscale * _grad_clip_factor(grad_norm, max_grad_norm)
        new_p, new_m, new_v, lowps = [], [], [], []
        for i, meta in enumerate(metas):
            p2, m2, v2, lp = fused_pipeline.adam_pipeline(
                gbufs[i], master_bufs[i], state.m[i], state.v[i],
                grad_scale=gscale, lr=lr, beta1=beta1, beta2=beta2,
                eps=eps, weight_decay=weight_decay,
                bias_correction1=bc1, bias_correction2=bc2,
                adam_w_mode=adam_w_mode, finite=finite,
                lowp_dtype=fused_pipeline.group_lowp_dtype(meta),
                use_pallas=use_pallas)
            new_p.append(p2)
            new_m.append(m2)
            new_v.append(v2)
            lowps.append(lp if lp is not None else p2)
        return (tuple(new_p),
                FusedAdamState(count, tuple(new_m), tuple(new_v)),
                lowps)

    return FusedTransformation(init, update, fused_step,
                               pipeline_init, pipeline_step,
                               (leaf_step, sumsq))


def _adam_jnp(g, p, m, v, lr, b1, b2, eps, wd, bc1, bc2, adam_w_mode):
    """Reference math in plain jnp (ref: csrc/multi_tensor_adam.cu:24-110)."""
    g = g.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    if not adam_w_mode:
        g = g + wd * p32
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w_mode:
        upd = upd + wd * p32
    return (-lr * upd).astype(p.dtype), m, v


FusedAdam = fused_adam
