"""Pipeline-parallel schedules: no-pipelining, 1F1B, interleaved.

TPU-native re-design of the reference's schedule zoo
(ref: apex/transformer/pipeline_parallel/schedules/__init__.py:16,
fwd_bwd_no_pipelining.py:29, fwd_bwd_pipelining_without_interleaving.py:22,
fwd_bwd_pipelining_with_interleaving.py:22).

The reference runs one Python process per stage and hand-schedules
warmup/steady(1F1B)/cooldown phases with NCCL p2p between them.  Under
XLA the whole pipeline is ONE program: a ``lax.scan`` over
``num_microbatches + num_stages - 1`` ticks inside ``shard_map`` over the
``pipe`` mesh axis.  Each tick, every stage applies its layer block to
its in-flight microbatch and hands the activation to its successor with
a single ``ppermute`` (ICI neighbour hop).  Bubble ticks (the triangle
the reference's warmup/cooldown phases walk) are masked compute — the
same utilization loss, expressed as data instead of control flow.

Reverse-mode AD through the scan yields the backward pipeline
automatically: ppermute transposes to the reverse hop, the scan reverses,
and each stage receives exactly the gradient exchange the reference
implements manually (send_backward_recv_backward).  Activation memory is
governed by ``jax.checkpoint`` on the stage function (``'full'`` policy
recomputes the block in backward — the reference's activation
checkpointing — bounding live activations per stage at the pipeline
depth, the same bound 1F1B provides).

Layout contract: stage parameters are stacked on a leading stage axis and
passed through ``shard_map`` with ``in_specs=P('pipe', ...)``; microbatch
inputs are ``[num_microbatches, micro_batch, ...]`` and replicated.  The
stage function must preserve the activation shape (uniform transformer
blocks); embedding and head run outside the pipelined region, matching
the reference's pre_process/post_process split
(ref: schedules/common.py:18-107).
"""
from __future__ import annotations

import functools
import warnings
from typing import Any, Callable, Optional

import jax
from ..._compat import axis_index, axis_size, pcast, psum_replicated, typeof
import jax.numpy as jnp

from ...mesh_plan import MeshPlan
from ...parallel_state import PIPE_AXIS
from ..tensor_parallel.random import CHECKPOINT_POLICIES
from . import p2p_communication


def pipeline_plan(num_stages: int, num_microbatches: int, *,
                  axis_name: str = PIPE_AXIS,
                  virtual_pipeline_size: Optional[int] = None,
                  with_backward: bool = True) -> MeshPlan:
    """The pipeline schedules' topology contract as data.

    One ``pipeline``-kind axis; stage parameters stacked on a leading
    stage axis and sharded over it; the collective budget prices the
    tick loop: every tick hands one activation to the successor with a
    single ``ppermute`` (2 per tick interleaved — activation feed plus
    the chunk-recirculation hop), over ``m + s·v - 1``-ish ticks, and
    training doubles it (the scan transposes every hop into the
    reverse ring).  The budget is a CEILING for the auditor's census,
    not an exact count — schedules may mask bubble ticks but never emit
    more hops than ticks.
    """
    v = virtual_pipeline_size or 1
    ticks = num_microbatches * v + num_stages - 1
    hops_per_tick = 2 if v > 1 else 1
    mult = 2 if with_backward else 1
    return MeshPlan.build(
        axes=((axis_name, num_stages, "pipeline"),),
        tensor_specs={
            # build_stage_params stacks per-stage trees on dim 0 (dim 0
            # is the vpp chunk when interleaving — the stage axis moves
            # to dim 1); both spell "one stage slice per device"
            r"stage": ((axis_name,) if v == 1 else (None, axis_name)),
        },
        collective_budget={"ppermute": ticks * hops_per_tick * mult})


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def pipeline_forward(stage_fn: Callable, stage_params: Any, microbatches: Any,
                     *, axis_name: str = PIPE_AXIS,
                     checkpoint_policy: Optional[str] = "full"):
    """Differentiable spatial pipeline over the ``pipe`` axis.

    Call inside ``shard_map``.  ``stage_fn(stage_params, x) -> y`` with
    ``y`` shaped like ``x``; ``microbatches`` is a pytree whose leaves
    are ``[M, ...]``.  Returns the last stage's outputs ``[M, ...]``,
    replicated over the axis (a psum of masked per-stage buffers).

    This is the single primitive behind both pipelined schedules —
    the reference's 1F1B tick structure
    (ref: fwd_bwd_pipelining_without_interleaving.py:61-170) appears
    here as the scan bounds: M + P - 1 ticks, microbatch ``t - rank``
    active on stage ``rank`` at tick ``t``.
    """
    nstages = axis_size(axis_name)
    rank = axis_index(axis_name)
    leaves = jax.tree.leaves(microbatches)
    num_micro = leaves[0].shape[0]

    fn = stage_fn
    if checkpoint_policy is not None:
        pol = (CHECKPOINT_POLICIES[checkpoint_policy]
               if isinstance(checkpoint_policy, str) else checkpoint_policy)
        fn = jax.checkpoint(stage_fn, policy=pol)

    def _varying(tree):
        # scan carries become axis-varying after the first ppermute/mask
        # (and inherit whatever varying axes the microbatch data carries,
        # e.g. 'data' when the batch is data-sharded); the initial zeros
        # must be marked identically for VMA type agreement
        def mark(x, ref):
            target = set(typeof(ref).vma) | {axis_name}
            missing = tuple(a for a in target if a not in typeof(x).vma)
            return pcast(x, missing, to="varying") if missing else x
        ref_leaves = jax.tree.leaves(jax.tree.map(lambda m: m[0],
                                                  microbatches))
        return jax.tree.map(
            mark, tree,
            jax.tree.unflatten(jax.tree.structure(tree), ref_leaves))

    first_mb = jax.tree.map(lambda x: x[0], microbatches)
    state0 = _varying(_tree_zeros_like(first_mb))
    out_shape = jax.eval_shape(lambda p, x: stage_fn(p, x),
                               stage_params, first_mb)
    jax.tree.map(lambda o, i: None if o.shape == i.shape else
                 (_ for _ in ()).throw(ValueError(
                     f"stage_fn must preserve activation shape, got "
                     f"{o.shape} from {i.shape}")), out_shape, first_mb)
    outputs0 = _varying(jax.tree.map(
        lambda x: jnp.zeros((num_micro,) + x.shape, x.dtype), first_mb))

    def tick(carry, t):
        state, outputs = carry
        mb_idx = t - rank
        feed_idx = jnp.clip(t, 0, num_micro - 1)
        fresh = jax.tree.map(
            lambda mb: jax.lax.dynamic_index_in_dim(mb, feed_idx, 0,
                                                    keepdims=False),
            microbatches)
        x = _tree_where(rank == 0, fresh, state)
        y = fn(stage_params, x)
        active = (mb_idx >= 0) & (mb_idx < num_micro)
        y = _tree_where(active, y, _tree_zeros_like(y))
        write_idx = jnp.clip(mb_idx, 0, num_micro - 1)
        write = (rank == nstages - 1) & active
        outputs = jax.tree.map(
            lambda buf, o: jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(buf, o, write_idx, 0),
                buf),
            outputs, y)
        state = jax.tree.map(
            lambda o: p2p_communication.send_forward_recv_forward(
                o, axis_name), y)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(
        tick, (state0, outputs0), jnp.arange(num_micro + nstages - 1))
    # Only the last stage wrote non-zeros; psum replicates to every
    # stage.
    return jax.tree.map(lambda o: psum_replicated(o, axis_name), outputs)


def forward_backward_no_pipelining(loss_fn: Callable, params: Any,
                                   microbatches: Any, *,
                                   forward_only: bool = False):
    """Grad accumulation over microbatches without pipelining
    (ref: fwd_bwd_no_pipelining.py:29-77): run every microbatch through
    ``loss_fn(params, microbatch) -> scalar``, averaging losses and
    gradients.  The reference defers the DDP allreduce to the last
    microbatch (no_sync); under pjit the psum placement after the scan
    achieves the same single gradient reduction.
    """
    def body(acc, mb):
        if forward_only:
            loss = loss_fn(params, mb)
            return acc, loss
        loss, grads = jax.value_and_grad(loss_fn)(params, mb)
        acc = jax.tree.map(jnp.add, acc, grads)
        return acc, loss

    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    acc0 = None if forward_only else _tree_zeros_like(params)
    if forward_only:
        _, losses = jax.lax.scan(lambda c, mb: body(None, mb), None,
                                 microbatches)
        return jnp.mean(losses), None
    acc, losses = jax.lax.scan(body, acc0, microbatches)
    grads = jax.tree.map(lambda g: g / num_micro, acc)
    return jnp.mean(losses), grads


def forward_backward_pipelining_without_interleaving(
        stage_fn: Callable, loss_fn: Callable, stage_params: Any,
        microbatches: Any, *, forward_only: bool = False,
        axis_name: str = PIPE_AXIS,
        checkpoint_policy: Optional[str] = "full"):
    """Pipelined fwd+bwd over the ``pipe`` axis (1F1B-equivalent;
    ref: fwd_bwd_pipelining_without_interleaving.py:22-170).

    ``loss_fn(outputs_mb, k)`` maps the last stage's activation for
    microbatch ``k`` to a scalar (it closes over labels).  Returns
    ``(mean_loss, grads)`` with grads structured like ``stage_params``
    (each stage's shard holds its own gradient — the per-rank layout the
    reference's per-process autograd produces).
    """
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]

    def total_loss(stage_params):
        outs = pipeline_forward(stage_fn, stage_params, microbatches,
                                axis_name=axis_name,
                                checkpoint_policy=checkpoint_policy)
        losses = jax.vmap(loss_fn)(outs, jnp.arange(num_micro))
        return jnp.mean(losses)

    if forward_only:
        return total_loss(stage_params), None
    loss, grads = jax.value_and_grad(total_loss)(stage_params)
    return loss, grads


def pipeline_forward_interleaved(stage_fn: Callable, chunk_params: Any,
                                 microbatches: Any, *,
                                 axis_name: str = PIPE_AXIS,
                                 checkpoint_policy: Optional[str] = "full"):
    """Interleaved (virtual-pipeline) forward: ONE scan, one block
    application per stage per tick, chunks overlapped in time.

    This is the true interleaved schedule, not sequential chunk sweeps:
    slot ``k`` of a stage processes chunk ``(k // P) %% vpp`` on
    microbatch ``(k // (vpp*P))*P + k %% P`` — the reference's
    chunk-major groups-of-P order
    (ref: fwd_bwd_pipelining_with_interleaving.py:100-140
    ``get_model_chunk_id``).  Stage ``s`` runs slot ``k`` at tick
    ``s + k``; a single *cyclic* ppermute per tick both feeds stage
    ``s+1`` and carries the chunk connector (last stage -> stage 0).
    Makespan is ``vpp*M + P`` ticks versus the sequential-sweep
    ``vpp*(M + P - 1)`` — the ``(vpp-1)*(P-1)`` bubble the interleaved
    schedule exists to remove is removed.
    """
    nstages = axis_size(axis_name)
    rank = axis_index(axis_name)
    vpp = jax.tree.leaves(chunk_params)[0].shape[0]
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    K = vpp * num_micro
    group = vpp * nstages

    fn = stage_fn
    if checkpoint_policy is not None:
        pol = (CHECKPOINT_POLICIES[checkpoint_policy]
               if isinstance(checkpoint_policy, str) else checkpoint_policy)
        fn = jax.checkpoint(stage_fn, policy=pol)

    def decode(k):
        """slot -> (chunk, microbatch) in chunk-major groups of P."""
        a = k // group
        rem = k % group
        c = rem // nstages
        m = a * nstages + rem % nstages
        return c, m

    def _varying(tree):
        def mark(x, ref):
            target = set(typeof(ref).vma) | {axis_name}
            missing = tuple(a for a in target
                            if a not in typeof(x).vma)
            return pcast(x, missing, to="varying") if missing \
                else x
        ref_leaves = jax.tree.leaves(jax.tree.map(lambda m: m[0],
                                                  microbatches))
        return jax.tree.map(
            mark, tree,
            jax.tree.unflatten(jax.tree.structure(tree), ref_leaves))

    first_mb = jax.tree.map(lambda x: x[0], microbatches)
    state0 = _varying(_tree_zeros_like(first_mb))
    outputs0 = _varying(jax.tree.map(
        lambda x: jnp.zeros((num_micro,) + x.shape, x.dtype), first_mb))

    def tick(carry, t):
        state, outputs = carry
        k = t - rank
        active = (k >= 0) & (k < K)
        c, m = decode(jnp.clip(k, 0, K - 1))

        params_c = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, c, 0,
                                                   keepdims=False),
            chunk_params)
        fresh = jax.tree.map(
            lambda mb: jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(m, 0, num_micro - 1), 0, keepdims=False),
            microbatches)
        # fresh data enters only at (stage 0, chunk 0); everything else
        # consumes the carry (pipeline input or chunk connector).
        x = _tree_where((rank == 0) & (c == 0), fresh, state)
        y = fn(params_c, x)
        y = _tree_where(active, y, _tree_zeros_like(y))

        # Collection: at stage 0, when the carry came from the last
        # stage's last chunk, it is a FINAL output for that microbatch.
        kprev = t - nstages
        cp, mp = decode(jnp.clip(kprev, 0, K - 1))
        collect = ((rank == 0) & (kprev >= 0) & (kprev < K)
                   & (cp == vpp - 1))
        wi = jnp.clip(mp, 0, num_micro - 1)
        outputs = jax.tree.map(
            lambda buf, s: jnp.where(
                collect,
                jax.lax.dynamic_update_index_in_dim(buf, s, wi, 0),
                buf),
            outputs, state)

        state = jax.tree.map(
            lambda o: p2p_communication.send_forward_recv_forward_cyclic(
                o, axis_name), y)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(
        tick, (state0, outputs0), jnp.arange(K + nstages))
    # Only stage 0 collected; psum replicates across the axis.
    return jax.tree.map(lambda o: psum_replicated(o, axis_name), outputs)


def forward_backward_pipelining_with_interleaving(
        stage_fn: Callable, loss_fn: Callable, stage_params: Any,
        microbatches: Any, *, forward_only: bool = False,
        axis_name: str = PIPE_AXIS,
        checkpoint_policy: Optional[str] = "full",
        strict: bool = False):
    """Virtual-pipeline (interleaved) schedule
    (ref: fwd_bwd_pipelining_with_interleaving.py:22-308).

    ``stage_params`` carries a leading virtual-chunk axis: chunk ``c`` of
    stage ``s`` owns layer block ``c * num_stages + s`` — the reference's
    round-robin model-chunk assignment (ref: parallel_state.py:101-108).
    Chunks execute overlapped (one scan, one block per stage per tick —
    see :func:`pipeline_forward_interleaved`); reverse-mode AD through
    the scan yields the interleaved backward order.

    The interleaved slot mapping requires ``M %% P == 0``.  Other M fall
    back to sequential chunk sweeps — same math, but the bubble the
    caller asked to remove is back, so the fallback WARNS;
    ``strict=True`` raises instead (the reference's behavior, which
    asserts ``num_microbatches %% pipeline_parallel_size == 0``).
    """
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    nstages = axis_size(axis_name)
    vpp = jax.tree.leaves(stage_params)[0].shape[0]
    if num_micro % nstages != 0:
        msg = (f"interleaved pipeline schedule needs num_microbatches "
               f"({num_micro}) divisible by pipeline stages ({nstages})"
               f"; falling back to sequential chunk sweeps — same "
               f"result, WITHOUT the interleaving bubble reduction")
        if strict:
            raise ValueError(msg.split(";")[0] + " (strict=True)")
        warnings.warn(msg, stacklevel=2)

    def total_loss(stage_params):
        if num_micro % nstages == 0:
            acts = pipeline_forward_interleaved(
                stage_fn, stage_params, microbatches,
                axis_name=axis_name,
                checkpoint_policy=checkpoint_policy)
        else:
            acts = microbatches
            for c in range(vpp):
                chunk = jax.tree.map(lambda p, c=c: p[c], stage_params)
                acts = pipeline_forward(
                    stage_fn, chunk, acts, axis_name=axis_name,
                    checkpoint_policy=checkpoint_policy)
        losses = jax.vmap(loss_fn)(acts, jnp.arange(num_micro))
        return jnp.mean(losses)

    if forward_only:
        return total_loss(stage_params), None
    loss, grads = jax.value_and_grad(total_loss)(stage_params)
    return loss, grads


def get_forward_backward_func(
        virtual_pipeline_model_parallel_size: Optional[int],
        pipeline_model_parallel_size: int):
    """Schedule selector (ref: schedules/__init__.py:16-29)."""
    if pipeline_model_parallel_size > 1:
        if virtual_pipeline_model_parallel_size is not None:
            return forward_backward_pipelining_with_interleaving
        return forward_backward_pipelining_without_interleaving
    return forward_backward_no_pipelining


def build_stage_params(init_fn: Callable, key: jax.Array, num_stages: int,
                       virtual_chunks: Optional[int] = None):
    """Stacked per-stage parameter construction — the functional analogue
    of the reference's ``build_model`` model-provider loop
    (ref: schedules/common.py:18-107): one init per (chunk, stage) with
    independent keys, stacked on leading [vpp?, stage] axes so
    ``shard_map`` in_specs ``P('pipe', ...)`` (after chunk indexing)
    place each stage's block on its devices.
    """
    chunks = virtual_chunks or 1
    keys = jax.random.split(key, chunks * num_stages)
    stacked = jax.vmap(init_fn)(keys)
    if virtual_chunks is None:
        return stacked
    return jax.tree.map(
        lambda x: x.reshape((chunks, num_stages) + x.shape[1:]), stacked)
