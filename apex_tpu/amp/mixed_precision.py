"""Mixed-precision optimizer wrapper: master weights + scaler + skip-on-inf.

This is the functional equivalent of the reference's optimizer surgery
(ref: apex/amp/_process_optimizer.py:28-256 — master-weight swap, patched
``step``/``zero_grad``, ``_post_amp_backward`` unscale) combined with the
``scale_loss`` exit path (ref: apex/amp/handle.py:118-158).  Instead of
monkey-patching a stateful optimizer, the whole per-step pipeline —
unscale, fused finite-check, conditional update, master->model writeback,
scale adjustment — is one pure function compiled into the train step.
Overflow skip is a ``lax.cond`` (both branches compiled once, no recompile
churn, no host sync).  The finite-check/skip applies to *dynamic* scaling;
static scales step unconditionally like the reference (see
``AmpOptimizer.check_finite``).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from . import cast as _cast
from . import scaler as _scaler
from ..ops import fused_pipeline as _pipeline
from ..ops import multi_tensor as _mt
from .policy import Policy, get_policy


class AmpState(NamedTuple):
    """Everything amp owns for one optimizer (a pytree).

    ``scalers`` is one :class:`ScalerState` per loss
    (ref: apex/amp/_initialize.py:227-231 creates ``num_losses`` scalers);
    masters and inner optimizer state are shared across losses, exactly as
    the reference shares one optimizer across ``loss_id``s.
    """

    inner_state: optax.OptState
    # fp32 master copy of params when the policy asks for master weights,
    # else None (inner optimizer then steps the model params directly).
    master_params: Optional[Any]
    scalers: Tuple[_scaler.ScalerState, ...]

    @property
    def scaler(self) -> _scaler.ScalerState:
        return self.scalers[0]


class StepInfo(NamedTuple):
    # With a *dynamic* scaler this is the measured finite flag; with a
    # static scaler gradients are not inspected (reference parity: the
    # static LossScaler steps regardless of overflow) and this reports
    # constant True.  ``grads_checked`` distinguishes the two: telemetry
    # that alerts on overflow must gate on ``grads_checked`` before
    # reading ``grads_finite`` — pass check_finite=True to AmpOptimizer
    # to measure (and skip) under static scaling too.
    grads_finite: jnp.ndarray
    loss_scale: jnp.ndarray
    steps_skipped: jnp.ndarray
    # Static (Python) flag: False when the step ran without inspecting
    # gradients, so grads_finite==True means "unchecked", not "healthy".
    grads_checked: bool = True
    # Unscaled global gradient L2 norm, measured by the fused
    # pipeline's norm sweep (None on the per-leaf path, which never
    # computes one).  Telemetry consumers (StepMonitor) read it from
    # here instead of re-sweeping the gradient tree host-side; under
    # shard_map it is the LOCAL shard's norm.
    grad_norm: Optional[jnp.ndarray] = None


class AmpOptimizer:
    """Pairs an optax ``GradientTransformation`` with a precision policy.

    Functional analogue of ``amp.initialize(model, optimizer, ...)``
    (ref: apex/amp/frontend.py:258): parameters stay in the policy's model
    dtype; fp32 masters live in :class:`AmpState`; gradients arriving at
    :meth:`apply_gradients` are the *scaled* gradients of a loss produced by
    :func:`scale_loss`.
    """

    def __init__(self, tx: optax.GradientTransformation, policy: Policy,
                 num_losses: int = 1, axis_names=None,
                 check_finite: Optional[bool] = None,
                 pipeline: Optional[bool] = None):
        self.tx = tx
        self.policy = policy
        self.num_losses = int(num_losses)
        self.use_masters = bool(policy.master_weights)
        # Where the masters and the optimizer state lie.  By default
        # (pipeline=None) every leaf keeps its own master and state in
        # its own shape: the fused optimizers' fused_step calls one
        # jitted leaf program a matrix leaf and one for all the vectors
        # (optimizers/fused_adam.py LeafProgram, _step_calls), so the
        # train step's tracing grows with the distinct leaf signatures
        # and no matrix is packed.  Packing lost wherever it was
        # measured on v5e: per-step packs at every size tried
        # (ops/multi_tensor.py's log), the persistent pack at 0.73x on
        # small trees, and at 345M the per-leaf step ran GPT-2's train
        # step at 142.1 ms against the packed 148.7 and BERT-large's at
        # 140.7 against 150.1 (the packing alone is 5.6 ms of GPT-2's
        # 15.7 ms optimizer: gradients laid into flat buffers, bf16
        # weights cut back out).
        # An EXPLICIT pipeline=True still builds the persistent packed
        # pipeline (ops/fused_pipeline.py: packed masters and state,
        # two fused sweeps); it needs master weights and an optimizer
        # with a pipeline form (fused_adam / fused_sgd / fused_lamb)
        # and raises without them — a silent per-leaf fallback would
        # corrupt packed-vs-per-leaf comparisons.
        capable = (self.use_masters
                   and getattr(tx, "pipeline_step", None) is not None)
        if pipeline and not capable:
            raise ValueError(
                "pipeline=True requires master weights (policy."
                "master_weights) and an optimizer with a pipeline form "
                f"(fused_adam/fused_sgd/fused_lamb); got policy "
                f"{policy.opt_level!r} master_weights="
                f"{bool(policy.master_weights)}, tx "
                f"{type(tx).__name__} with pipeline_step="
                f"{getattr(tx, 'pipeline_step', None)}")
        self._pipeline_explicit = pipeline is True
        # What the last traced step did, static facts no metric reads
        # (docs/api/observability.md): ``route`` ("per_leaf" or
        # "packed", from the state's layout), ``leaves_stepped`` (the
        # model tree's leaves); ``leaf_programs`` (a property) counts
        # the leaf programs the optimizer has traced.
        self.route: Optional[str] = None
        self.leaves_stepped = 0
        # Model-parallel axes to reduce the found-inf flag over, so every
        # shard takes the same skip-vs-step branch (ref:
        # apex/transformer/amp/grad_scaler.py:25-36).  Only meaningful
        # when apply_gradients runs inside shard_map over these axes.
        self.axis_names = axis_names
        # None (default) = reference parity: inspect gradients only under
        # dynamic scaling (apex's static LossScaler never skips a step —
        # ref: apex/amp/scaler.py update_scale, should_skip only when
        # dynamic).  True forces the finite-check + skip even for static
        # scales (costs a full pass over the gradients: measured
        # 14 ms/step on GPT-345M @ v5e).  False is rejected for dynamic
        # scalers, whose scale schedule needs the flag.
        self.check_finite = check_finite

    # -- lifecycle ----------------------------------------------------------

    def init(self, params: Any) -> AmpState:
        """Build amp state.  Pass the *original* (highest-precision) params
        here, not the already-cast copy — masters are snapshotted exactly
        from them (the reference likewise clones masters from the fp32
        model before it is cast, ref: apex/amp/_process_optimizer.py:28-44).
        """
        if self._pipeline_explicit:
            # Persistent packed mode: the master "tree" is a
            # PackedMasters (flat fp32 buffers + static layout), the
            # inner state packs into the same layout.  The layout is
            # computed from the CAST model template so per-step
            # gradient packing groups identically.
            masters = _pipeline.pack_masters(
                params, _cast.cast_params(params, self.policy))
            inner = self.tx.pipeline_init(masters.metas)
        elif self.use_masters:
            masters = _cast.master_copy(params)
            inner = self.tx.init(masters)
        else:
            masters = None
            # Inner state dtypes must match what will actually be stepped
            # (the cast model params, e.g. fp16 under O3).
            inner = self.tx.init(_cast.cast_params(params, self.policy))
        return AmpState(
            inner_state=inner,
            master_params=masters,
            scalers=tuple(
                _scaler.init(self.policy.effective_loss_scale)
                for _ in range(self.num_losses)
            ),
        )

    @property
    def leaf_programs(self) -> int:
        """Leaf programs the optimizer has traced: one per distinct leaf
        signature and program kind (the per-leaf step, and the norm's
        sum of squares where a clip is on); 0 for an optimizer without
        them and on the packed route."""
        return sum(len(p.signatures)
                   for p in getattr(self.tx, "leaf_programs", ()))

    # -- per-iteration hooks ------------------------------------------------

    def scale_loss(self, loss: jnp.ndarray, state: AmpState,
                   loss_id: int = 0) -> jnp.ndarray:
        """``with amp.scale_loss(..., loss_id=i)`` entry
        (ref: apex/amp/handle.py:16)."""
        return _scaler.scale_loss(loss, state.scalers[loss_id])

    def apply_gradients(
        self, scaled_grads: Any, state: AmpState, params: Any,
        loss_id: int = 0, axis_names=None,
    ) -> Tuple[Any, AmpState, StepInfo]:
        """Unscale, check, conditionally step, writeback, update scale.

        Returns ``(new_params, new_state, info)``.  The skipped branch
        returns params/state unchanged (the reference's patched-no-op
        ``optimizer.step``, ref: apex/amp/handle.py:128-154).  With
        multiple losses, call once per loss with the matching ``loss_id``;
        masters/inner state advance each call, scalers independently.
        ``axis_names`` (default ``None`` = use the constructor's)
        reduces the finite flag over those mesh axes before branching,
        so model-parallel shards skip or step in lockstep.  Pass ``()``
        to explicitly disable the reduction for this call (e.g. when
        stepping the same optimizer outside shard_map).
        """
        # Dispatch on the STATE's layout, not the constructor flag: a
        # checkpoint-restored state must step the way it was built.
        # Either way every op of the post-backward step carries the
        # scope ``apex.optimizer`` in its name (metadata only: the
        # compiled program is the same; a leaf program's ops read
        # ``apex.optimizer/jit(<program>)/...`` once the TPU compiler
        # has inlined its calls), which is how a device trace gives
        # the optimizer's time.
        packed = isinstance(state.master_params, _pipeline.PackedMasters)
        apply = (self._apply_gradients_pipeline if packed
                 else self._apply_gradients_per_leaf)
        self.route = "packed" if packed else "per_leaf"
        self.leaves_stepped = len(jax.tree_util.tree_leaves(params))
        with jax.named_scope("apex.optimizer"):
            return apply(scaled_grads, state, params, loss_id,
                         axis_names)

    def _apply_gradients_per_leaf(self, scaled_grads, state, params,
                                  loss_id, axis_names):
        """The per-leaf post-backward step: finite check, conditional
        update, master -> model cast.  A fused optimizer takes the
        scaled gradients and amp's inverse loss scale as its
        ``grad_scale``: the unscale is one multiply inside each leaf
        program, not a tree of its own (exact either way: the scale
        schedule only holds powers of two).  Any other optimizer gets
        an unscaled fp32 gradient tree."""
        scaler = state.scalers[loss_id]
        inv = (1.0 / scaler.loss_scale).astype(jnp.float32)
        # Single-pass optimizers (FusedTransformation.fused_step) apply
        # the update AND emit the low-precision model copy inside the
        # update's fusion scope — XLA does not multi-output-fuse a
        # separate restore_dtypes pass (measured 2.1 ms/step of pure
        # master->bf16 convert at GPT-345M).
        fused = getattr(self.tx, "fused_step", None)
        grads = (scaled_grads if fused is not None
                 else _scaler.unscale(scaled_grads, scaler))
        if axis_names is None:
            axis_names = self.axis_names

        stepped = state.master_params if self.use_masters else params

        def do_step(operand):
            grads_, inner_, stepped_, model_ = operand
            if fused is not None:
                new_stepped, new_inner, new_model = fused(
                    grads_, inner_, stepped_,
                    model_params=model_ if self.use_masters else None,
                    grad_scale=inv)
            else:
                g = _grads_like(grads_, stepped_)
                updates, new_inner = self.tx.update(g, inner_, stepped_)
                new_stepped = optax.apply_updates(stepped_, updates)
                new_model = None
            if self.use_masters and new_model is None:
                # Master -> model writeback: emit params in the model
                # dtype (ref: apex/amp/_process_optimizer.py:14-25).
                new_model = _cast.restore_dtypes(new_stepped, model_)
            return new_stepped, new_inner, new_model

        check = self._resolve_check(scaler)
        if not check:
            # Static scaling never inspects gradients: the reference's
            # static LossScaler steps regardless of overflow
            # (ref: apex/amp/scaler.py update_scale — should_skip only
            # when dynamic; O4/O5 pin loss_scale=1).  Skipping the
            # grad-wide isfinite reduction saves a full pass over the
            # gradients (measured 14 ms/step on GPT-345M @ v5e).
            # StepInfo.grads_finite then reports constant True
            # ("unchecked") — see StepInfo.
            finite = jnp.bool_(True)
            new_stepped, new_inner, new_model = do_step(
                (grads, state.inner_state, stepped, params))
        else:
            # the flag of the unscaled gradients, as the update sees
            # them; the product fuses into the reduction
            finite = _scaler.all_finite(
                grads if fused is None else jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) * inv, grads),
                axis_names=axis_names)

            def skip_step(operand):
                _, inner_, stepped_, model_ = operand
                # mirror do_step's writeback so both branches emit the
                # same structure/shapes (a skipped step re-casts the
                # unchanged masters — bitwise the old model params)
                model_out = _cast.restore_dtypes(stepped_, model_) \
                    if self.use_masters else None
                return stepped_, inner_, model_out

            new_stepped, new_inner, new_model = jax.lax.cond(
                finite, do_step, skip_step,
                (grads, state.inner_state, stepped, params))

        if self.use_masters:
            new_params = new_model
            new_masters = new_stepped
        else:
            new_params = new_stepped
            new_masters = None

        new_scaler = _scaler.update(state.scalers[loss_id], finite)
        new_scalers = tuple(
            new_scaler if i == loss_id else s
            for i, s in enumerate(state.scalers)
        )
        new_state = AmpState(new_inner, new_masters, new_scalers)
        return new_params, new_state, StepInfo(
            grads_finite=finite,
            loss_scale=new_scaler.loss_scale,
            steps_skipped=new_scaler.steps_skipped,
            grads_checked=check,
        )

    def _resolve_check(self, scaler) -> bool:
        """Static decision: inspect gradients this step?  None
        (default) = reference parity — only under dynamic scaling
        (apex's static LossScaler never skips); True forces the check;
        False is rejected for dynamic scalers."""
        check = self.check_finite
        if check is None:
            return scaler.dynamic
        if not check and scaler.dynamic:
            raise ValueError("check_finite=False is invalid with a dynamic "
                             "loss scaler: the scale schedule needs the "
                             "finite flag")
        return check

    def _apply_gradients_pipeline(self, scaled_grads, state, params,
                                  loss_id, axis_names):
        """The persistent-packed post-backward step: TWO fused sweeps
        instead of the per-leaf unscale / finite-check / update /
        master->model chain (see ops/fused_pipeline.py).

        Sweep 1 reads the packed grads once, producing the unscaled
        global norm and the finite flag (the multi_tensor_l2norm +
        overflow-buffer roles); sweep 2 reads grads+masters+state and
        writes masters+state+model-copy, with the unscale (and any
        optimizer clip) folded into its combined scale and the
        overflow skip as an in-sweep select.  Skip semantics match the
        per-leaf ``lax.cond`` bitwise: state unchanged, model re-cast
        from the unchanged masters.

        Static scaling steps unconditionally (``_resolve_check``) AND
        elides the norm/finite sweep entirely — the per-leaf path
        deliberately skips that grad-wide pass (measured 14 ms/step at
        GPT-345M) and the pipeline must not re-add it; StepInfo.
        grad_norm is then None (telemetry falls back) and any
        optimizer-level clip derives its own norm inside the update
        path.
        """
        scaler = state.scalers[loss_id]
        if axis_names is None:
            axis_names = self.axis_names
        masters = state.master_params
        metas = masters.metas
        gbufs = _pipeline.pack_grads(scaled_grads, metas)
        inv = (1.0 / scaler.loss_scale).astype(jnp.float32)
        check = self._resolve_check(scaler)
        if check:
            gnorm, finite_measured = _pipeline.grad_norm_finite(gbufs,
                                                                inv)
            finite = _scaler.reduce_finite(finite_measured, axis_names)
        else:
            gnorm, finite = None, jnp.bool_(True)
        new_mbufs, new_inner, lowp = self.tx.pipeline_step(
            gbufs, state.inner_state, masters.bufs, metas,
            grad_scale=inv, grad_norm=gnorm, finite=finite)
        model_leaves = jax.tree_util.tree_leaves(params)
        new_params = _mt.assemble(
            lowp, list(metas),
            out_dtypes=[jnp.asarray(l).dtype for l in model_leaves])
        new_masters = _pipeline.PackedMasters(tuple(new_mbufs), metas)
        new_scaler = _scaler.update(scaler, finite)
        new_scalers = tuple(
            new_scaler if i == loss_id else s
            for i, s in enumerate(state.scalers))
        new_state = AmpState(new_inner, new_masters, new_scalers)
        return new_params, new_state, StepInfo(
            grads_finite=finite,
            loss_scale=new_scaler.loss_scale,
            steps_skipped=new_scaler.steps_skipped,
            grads_checked=check,
            grad_norm=gnorm,
        )

    # -- checkpointing (ref: apex/amp/frontend.py:428-454) ------------------

    def state_dict(self, state: AmpState) -> dict:
        """Serialize every loss scaler (ref: apex/amp/frontend.py:428-437
        loops over ``_amp_state.loss_scalers``)."""
        d = {"scalers": [_scaler.state_dict(s) for s in state.scalers]}
        d["scaler"] = d["scalers"][0]  # convenience alias
        return d

    def load_state_dict(self, state: AmpState, d: dict) -> AmpState:
        if "scalers" in d:
            return state._replace(scalers=tuple(
                _scaler.load_state_dict(sd) for sd in d["scalers"]))
        return state._replace(
            scalers=(_scaler.load_state_dict(d["scaler"]),))


def _grads_like(grads32: Any, ref_tree: Any) -> Any:
    """Cast fp32 grads to match the stepped tree's leaf dtypes (inner
    optimizers expect updates in param dtype)."""
    return jax.tree_util.tree_map(
        lambda g, p: g.astype(jnp.asarray(p).dtype), grads32, ref_tree)


def initialize(
    params: Any,
    optimizer: optax.GradientTransformation,
    opt_level: str = "O5",
    num_losses: int = 1,
    axis_names=None,
    check_finite: Optional[bool] = None,
    pipeline: Optional[bool] = None,
    **overrides,
) -> Tuple[Any, AmpOptimizer, Any]:
    """The two-line setup entry, mirroring
    ``model, opt = amp.initialize(model, opt, opt_level=...)``
    (ref: apex/amp/frontend.py:258).

    Returns ``(cast_params, amp_optimizer, amp_state)``.  The state holds
    ``num_losses`` independent scalers (ref: apex/amp/_initialize.py:227-231)
    over one shared master copy + inner optimizer state; masters are
    snapshotted from the original ``params`` *before* the low-precision
    cast, so no precision is lost at initialization.
    """
    policy = get_policy(opt_level, **overrides)
    cast = _cast.cast_params(params, policy)
    amp_opt = AmpOptimizer(optimizer, policy, num_losses=num_losses,
                           axis_names=axis_names,
                           check_finite=check_finite,
                           pipeline=pipeline)
    return cast, amp_opt, amp_opt.init(params)
