"""DistributedFusedAdam — ZeRO-sharded Adam over the data axis.

TPU-native equivalent of the reference's distributed optimizer family
(ref: apex/contrib/optimizers/distributed_fused_adam.py /_v2/_v3):
instead of the reference's hand-pipelined flat-buffer
``reduce_scatter`` + inter-node allreduce on dedicated process groups
with backward-hook overlap (ref: distributed_fused_lamb.py:590-612
``_pipeline_block_reductions``; same structure in the adam variants),
the JAX formulation is three collectives XLA schedules freely:

    grad shard   = psum_scatter(flat_grads) / world     (ZeRO reduce)
    state update = fused Adam on the 1/N shard          (sharded m, v)
    new params   = all_gather(delta shards)             (param sync)

Optimizer state (m, v) only ever exists shard-sized — the ZeRO memory
saving.  Must be called inside ``shard_map`` over ``axis_name``; init
must also run in that context (shard sizes depend on the axis size).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
from ..._compat import axis_index, axis_size
import jax.numpy as jnp
import optax

from ...mesh_plan import MeshPlan
from ...ops import fused_optim, multi_tensor
from ...optimizers.fused_adam import ScalarOrSchedule, _adam_jnp, _lr_at


class DistributedFusedAdamState(NamedTuple):
    count: jnp.ndarray
    m: Tuple[jnp.ndarray, ...]   # 1/N shard per dtype group (fp32)
    v: Tuple[jnp.ndarray, ...]


def _shard_padded(meta: multi_tensor.FlatMeta, world: int) -> int:
    """Padded group length divisible by world * LANE-tile."""
    unit = world * multi_tensor._PAD_TO
    return -(-meta.padded // unit) * unit


def zero_adam_plan(world: int, num_groups: int = 1, *,
                   axis_name: str = "data") -> MeshPlan:
    """The ZeRO topology contract as data: ONE ``zero``-kind axis; the
    optimizer state (``m``/``v`` flat buffers) sharded 1/world over it
    — the memory saving that IS ZeRO, and exactly what a replicated-
    state regression silently destroys (rule APX701); params and the
    pre-reduce grads full per device; one psum_scatter (grad reduce)
    plus one all_gather (delta sync) per dtype group per step.

    Declaring the state spec here is what turned up the real finding
    this plan shipped with: the ZeRO bench driver carried the sharded
    state through its shard_map boundary as ``P()`` (replicated) —
    right on its 1-device bench mesh, silently wrong on any real one.
    The boundary specs now derive from this plan
    (``plan.partition_spec``)."""
    return MeshPlan.build(
        axes=((axis_name, world, "zero"),),
        tensor_specs={
            # the sharded flat state buffers: global (padded,) arrays,
            # one 1/world slice per device (matched on NamedTuple field
            # names — state.m / state.v — however the entry spells its
            # argument paths)
            r"\.(m|v)\b": (axis_name,),
            # scalar step count: replicated
            r"\.count\b": (),
        },
        # psum_scatter traces as the reduce_scatter primitive — the
        # census speaks jaxpr
        collective_budget={"reduce_scatter": num_groups,
                           "all_gather": num_groups})


def distributed_fused_adam(
        learning_rate: ScalarOrSchedule = 1e-3,
        beta1: float = 0.9,
        beta2: float = 0.999,
        eps: float = 1e-8,
        weight_decay: float = 0.0,
        adam_w_mode: bool = True,
        bias_correction: bool = True,
        axis_name: str = "data",
        grad_average: bool = True,
        use_pallas: bool = None) -> optax.GradientTransformation:
    """Build the sharded transformation.  ``update`` receives *local*
    (unreduced) gradients — the reduce is fused into the scatter."""

    def init(params):
        world = axis_size(axis_name)
        metas = multi_tensor.compute_metas(params)
        shards = tuple(
            jnp.zeros((_shard_padded(m, world) // world,), jnp.float32)
            for m in metas)
        return DistributedFusedAdamState(
            count=jnp.zeros((), jnp.int32),
            m=shards, v=tuple(jnp.zeros_like(s) for s in shards))

    def update(grads, state, params=None):
        fused = use_pallas if use_pallas is not None \
            else jax.default_backend() == "tpu"
        if params is None:
            raise ValueError("distributed_fused_adam requires params")
        world = axis_size(axis_name)
        rank = axis_index(axis_name)
        count = state.count + 1
        lr = _lr_at(learning_rate, count)
        cf = count.astype(jnp.float32)
        if bias_correction:
            bc1 = 1.0 - jnp.float32(beta1) ** cf
            bc2 = 1.0 - jnp.float32(beta2) ** cf
        else:
            bc1 = bc2 = jnp.float32(1.0)

        metas = multi_tensor.compute_metas(params)
        gbufs = multi_tensor.pack(grads, metas)
        pbufs = multi_tensor.pack(params, metas)
        deltas, new_m, new_v = [], [], []
        for i, meta in enumerate(metas):
            padded = _shard_padded(meta, world)
            shard = padded // world
            g = gbufs[i].astype(jnp.float32)
            if padded != meta.padded:
                g = jnp.pad(g, (0, padded - meta.padded))
            # ZeRO reduce: each device keeps the summed 1/N shard
            # (ref: _pipeline_block_reductions reduce_scatter stage).
            g_shard = jax.lax.psum_scatter(g, axis_name,
                                           scatter_dimension=0, tiled=True)
            if grad_average:
                g_shard = g_shard / world
            p = pbufs[i]
            if padded != meta.padded:
                p = jnp.pad(p, (0, padded - meta.padded))
            p_shard = jax.lax.dynamic_slice_in_dim(p, rank * shard, shard)
            if fused:
                d, m, v = fused_optim.adam_update(
                    g_shard, p_shard, state.m[i], state.v[i],
                    lr=lr, beta1=beta1, beta2=beta2, eps=eps,
                    weight_decay=weight_decay,
                    bias_correction1=bc1, bias_correction2=bc2,
                    adam_w_mode=adam_w_mode)
            else:
                d, m, v = _adam_jnp(g_shard, p_shard, state.m[i],
                                    state.v[i], lr, beta1, beta2, eps,
                                    weight_decay, bc1, bc2, adam_w_mode)
            # Param sync: gather delta shards back to the full buffer
            # (ref: param all_gather after step,
            # distributed_fused_adam.py _pipeline_step).
            full = jax.lax.all_gather(d.astype(jnp.float32), axis_name,
                                      tiled=True)
            deltas.append(full[:meta.padded])
            new_m.append(m)
            new_v.append(v)
        leaves = jax.tree_util.tree_leaves(params)
        updates = multi_tensor.unpack_groups(
            deltas, metas, out_dtypes=[l.dtype for l in leaves])
        return updates, DistributedFusedAdamState(
            count, tuple(new_m), tuple(new_v))

    return optax.GradientTransformation(init, update)


DistributedFusedAdam = distributed_fused_adam
