"""Expert-parallel serving: MoE decode sharded along a MeshPlan
``expert`` axis (ISSUE-19 tentpole, piece 3).

The serving step functions (:mod:`.model`) duck-type MoE layers on
``MoELayerWeights.router`` and route through ``_moe_mlp``, whose
collective points arm when ``ServingModelConfig.ep_axis`` is set.
This module supplies the topology as data, the same way
:mod:`.tp` does for tensor parallelism:

* :func:`serving_ep_plan` — the :class:`~apex_tpu.mesh_plan.MeshPlan`
  contract: one ``expert``-kind axis; ONLY the expert stacks
  (``wi``/``wo``) shard (leading expert dim), everything else —
  attention, router, layer norms, embeddings, the paged KV cache —
  stays replicated; and the collective budget: **2·chunks all_to_all
  plus 1 psum per MoE layer** (the capacity-chunked overlapped
  dispatch/return exchange of
  :func:`~apex_tpu.transformer.expert_parallel.
  moe_dispatch_combine_fused`, then one masked psum replicating the
  combined token slice), a CEILING the SPMD auditor holds the
  compiled artifact to.
* :class:`EPContext` — binds a plan to devices and builds the
  shard_map-wrapped, donation-preserving jitted step builders the
  :class:`~.engine.ServingEngine` swaps in: same signatures, same
  bucket ladder, same AOT warmup — expert parallelism is invisible
  to the continuous-batching loop.

Unlike TP (which shards per-token work), EP shards per-EXPERT work:
each rank holds ``E/ep`` expert FFNs and the full attention stack, so
attention/cache math is redundantly replicated while the dominant MoE
FFN FLOPs and weights split.  Tokens slice ``T/ep`` per rank before
routing; the post-psum combined activations are shard-invariant, so
greedy argmax samples the same token everywhere and the engine's one
fetch per tick is unchanged.  The audited entry
(``gpt_decode_step_ep`` in :mod:`apex_tpu.testing.entry_points`)
carries this plan, so APX701/703/705 guard the serving topology and
tests pin the EP engine's greedy output token-identical to the
single-chip engine on a duplicated-expert config.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence

from ..mesh_plan import MeshPlan
from .kv_cache import KVCacheConfig, init_cache
from .model import (GPTServingWeights, MoELayerWeights,
                    ServingModelConfig, gpt_decode_step,
                    gpt_extend_step, gpt_prefill_step)

__all__ = ["SERVING_EP_AXIS", "EPContext", "expand_moe_weights",
           "serving_ep_plan"]

# the canonical serving expert-axis name (MeshPlan kind "expert")
SERVING_EP_AXIS = "expert"


def serving_ep_plan(ep: int, num_layers: int, *,
                    axis: str = SERVING_EP_AXIS,
                    a2a_chunks: int = 2) -> MeshPlan:
    """The EP serving topology contract for the audited decode entry:
    expert stacks sharded on their leading (expert) dim under ``in0``,
    the router and every dense/attention tensor replicated by
    omission, the paged cache replicated in AND out, and the
    per-layer collective ceiling — ``2·a2a_chunks`` all_to_all (the
    overlapped dispatch + return hops of the capacity-chunked
    exchange) plus one masked psum (slice replication).  The runtime
    (:class:`EPContext`) derives its shard_map in/out specs and jit
    in_shardings from THIS object, so plan drift is an APX703
    finding, not a silent reshard."""
    if a2a_chunks < 1:
        raise ValueError(f"a2a_chunks {a2a_chunks} must be >= 1")
    specs = {
        r"^in0.*\.wi$": (axis,),
        r"^in0.*\.wo$": (axis,),
    }
    n_layers = int(num_layers)
    return MeshPlan.build(
        axes=((axis, int(ep), "expert"),),
        tensor_specs=specs,
        collective_budget={
            "all_to_all": 2 * int(a2a_chunks) * n_layers,
            "psum": n_layers,
        })


def expand_moe_weights(weights: GPTServingWeights, num_experts: int,
                       rng=None) -> GPTServingWeights:
    """Convert dense serving weights into a ``num_experts``-way MoE
    model: every layer's fc1/fc2 kernel is TILED into the
    ``(E, H, F)`` / ``(E, F, H)`` expert stacks (all experts start
    identical — the dense function, which is what the token-parity
    tests rely on) and a small random router is drawn per layer
    (``rng`` a PRNGKey; zeros when None, making routing uniform and
    the expansion fully deterministic).  fc biases are dropped — the
    serving MoE expert stacks are bias-free (matching
    :class:`~apex_tpu.transformer.layers_moe.MoEMLP`) — so exact
    dense equivalence needs zero fc biases in the source weights."""
    import jax
    import jax.numpy as jnp

    e = int(num_experts)
    if e < 1:
        raise ValueError(f"num_experts {e} must be >= 1")
    layers = []
    for i, lw in enumerate(weights.layers):
        h = lw.fc1_k.shape[0]
        if rng is None:
            router = jnp.zeros((h, e), jnp.float32)
        else:
            router = 0.02 * jax.random.normal(
                jax.random.fold_in(rng, i), (h, e), jnp.float32)
        layers.append(MoELayerWeights(
            ln1_w=lw.ln1_w, ln1_b=lw.ln1_b,
            qkv_k=lw.qkv_k, qkv_b=lw.qkv_b,
            dense_k=lw.dense_k, dense_b=lw.dense_b,
            ln2_w=lw.ln2_w, ln2_b=lw.ln2_b,
            router=router,
            wi=jnp.broadcast_to(lw.fc1_k[None], (e,) + lw.fc1_k.shape
                                ).copy(),
            wo=jnp.broadcast_to(lw.fc2_k[None], (e,) + lw.fc2_k.shape
                                ).copy(),
        ))
    return weights._replace(layers=tuple(layers))


def _keystr(path) -> str:
    import jax

    return jax.tree_util.keystr(path)


class EPContext:
    """One expert-parallel serving topology, bound to real devices.

    Validates the geometry (``model_cfg.num_experts`` must be set and
    divide by ``ep``; the cache must match the model's head layout —
    it is replicated, never split), builds the mesh from ``devices``
    (default: the first ``ep`` of ``jax.devices()``), and exposes
    exactly what the engine needs:

    * :meth:`shard_weights` / :meth:`init_cache` — commit the global
      arrays to their plan shardings once (expert stacks split,
      everything else replicated), so every step call runs
      reshard-free;
    * :meth:`jit_decode` / :meth:`jit_prefill` / :meth:`jit_extend` —
      drop-in replacements for the engine's single-chip jit builders:
      same signatures, cache donated, shard_map inside with in/out
      specs derived from the plan.

    ``model_cfg`` is the context's ep-axis-carrying config — the
    engine serves with it so ``_moe_mlp``'s token slicing, overlapped
    exchange, and masked psum are armed."""

    def __init__(self, model_cfg: ServingModelConfig,
                 cache_cfg: KVCacheConfig, ep: int, *,
                 axis: str = SERVING_EP_AXIS,
                 devices: Optional[Sequence[Any]] = None):
        if model_cfg.family != "gpt2":
            raise ValueError(
                f"family {model_cfg.family!r} has no expert-parallel "
                f"serving forward yet; serve it on one chip"
                + (" (it computes the share of the experts that "
                   "expert_first and its stacks name, and no exchange)"
                   if model_cfg.mla is not None else ""))
        if cache_cfg.pooled:
            raise ValueError(
                "the pooled cache (KVCacheConfig.window) has no "
                "expert-parallel serving forward yet; serve it on one chip")
        if ep < 2:
            raise ValueError(f"ep {ep} must be >= 2 (ep=1 is the "
                             f"single-chip engine, no context needed)")
        if model_cfg.num_experts < 1:
            raise ValueError(
                "EPContext needs an MoE model: "
                f"model_cfg.num_experts={model_cfg.num_experts}")
        if model_cfg.num_experts % ep:
            raise ValueError(
                f"num_experts {model_cfg.num_experts} not divisible "
                f"by ep {ep}")
        if model_cfg.tp_axis is not None:
            raise ValueError(
                "EPContext does not compose with tp_axis "
                f"{model_cfg.tp_axis!r} — expert parallelism "
                "replicates the attention stack")
        if cache_cfg.num_heads != model_cfg.num_heads \
                or cache_cfg.head_dim != model_cfg.head_dim:
            raise ValueError(
                "cache_cfg head geometry "
                f"({cache_cfg.num_heads}x{cache_cfg.head_dim}) does "
                f"not match the model "
                f"({model_cfg.num_heads}x{model_cfg.head_dim})")
        self.ep = int(ep)
        self.axis = axis
        self.cache_cfg = cache_cfg
        # the cache is replicated over the expert axis — per-shard
        # geometry IS the global geometry (contrast TPContext's
        # head-split local_cache_cfg)
        self.local_cache_cfg = cache_cfg
        self.model_cfg = dataclasses.replace(model_cfg, ep_axis=axis)
        self.plan = serving_ep_plan(
            ep, model_cfg.num_layers, axis=axis,
            a2a_chunks=model_cfg.moe_a2a_chunks)
        self.mesh = self.plan.make_mesh(devices)

    # --- spec trees -----------------------------------------------------

    def _replicated(self):
        from jax.sharding import PartitionSpec as P

        return P()

    def _spec_tree(self, tree, prefix: str):
        """PartitionSpec pytree for ``tree`` from the plan's declared
        specs under ``prefix`` — the ONE derivation both shard_map
        in/out_specs and jit in/out_shardings use."""
        import jax

        return jax.tree_util.tree_map_with_path(
            lambda path, _: self.plan.partition_spec(
                prefix + _keystr(path)), tree)

    def weight_specs(self, weights: GPTServingWeights):
        return self._spec_tree(weights, "in0")

    def cache_specs(self, cache=None):
        """PartitionSpec pytree for the paged cache — every leaf
        replicated (the plan declares no ``in1`` patterns): each
        expert shard holds the full cache and runs the full attention
        stack."""
        if cache is None:
            cache = init_cache(self.cache_cfg)
        return self._spec_tree(cache, "in1")

    def _named(self, spec_tree):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            spec_tree, is_leaf=lambda s: isinstance(s, P))

    # --- committed placement -------------------------------------------

    def shard_weights(self, weights: GPTServingWeights
                      ) -> GPTServingWeights:
        """Commit the (global) weight arrays to their plan shardings —
        expert stacks split on their leading dim, everything else
        replicated — once at engine construction and once per weight
        swap, so steps never pay a per-call reshard."""
        import jax

        for lw in weights.layers:
            if getattr(lw, "router", None) is None:
                raise ValueError(
                    "EPContext weights must be MoE layers "
                    f"(got {type(lw).__name__}; run "
                    "expand_moe_weights first)")
        return jax.device_put(weights,
                              self._named(self.weight_specs(weights)))

    def init_cache(self):
        """A zeroed paged cache committed replicated — every shard
        writes/reads the full cache (attention is redundant under
        EP)."""
        import jax

        cache = init_cache(self.cache_cfg)
        return jax.device_put(cache,
                              self._named(self.cache_specs(cache)))

    # --- jitted step builders (engine drop-ins) -------------------------

    def _wrap(self, body, weights, n_data: int):
        """shard_map-wrapped jit: ``body(weights, cache, *data)`` with
        the expert stacks sharded per plan, cache and the ``n_data``
        trailing args replicated, every output replicated (post-psum
        values are shard-invariant), and the cache donated.
        ``check_vma=False`` — the overlapped exchange's custom_vjp and
        the masked psum predate the replication-rewrite trace (see
        ``_chunked_expert_exchange``)."""
        import jax

        from .._compat import shard_map

        rep = self._replicated()
        w_specs = self.weight_specs(weights)
        c_specs = self.cache_specs()
        in_specs = (w_specs, c_specs) + (rep,) * n_data
        out_specs = (c_specs, rep)
        in_sh = (self._named(w_specs), self._named(c_specs)) \
            + (self._named(rep),) * n_data
        out_sh = (self._named(c_specs), self._named(rep))
        mesh = self.mesh

        @functools.partial(jax.jit, donate_argnums=(1,),
                           in_shardings=in_sh, out_shardings=out_sh)
        def step(weights, cache, *data):
            return shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs,
                             check_vma=False)(weights, cache, *data)

        return step

    def jit_decode(self, weights: GPTServingWeights):
        cfg, ccfg = self.model_cfg, self.local_cache_cfg

        def body(weights, cache, tokens, positions, block_tables,
                 seq_lens, write_blocks, write_offsets):
            return gpt_decode_step(weights, cfg, ccfg, cache, tokens,
                                   positions, block_tables, seq_lens,
                                   write_blocks, write_offsets)

        return self._wrap(body, weights, 6)

    def jit_prefill(self, weights: GPTServingWeights):
        cfg, ccfg = self.model_cfg, self.local_cache_cfg

        def body(weights, cache, tokens, length, blocks):
            return gpt_prefill_step(weights, cfg, ccfg, cache, tokens,
                                    length, blocks)

        return self._wrap(body, weights, 3)

    def jit_extend(self, weights: GPTServingWeights):
        cfg, ccfg = self.model_cfg, self.local_cache_cfg

        def body(weights, cache, tokens, block_tables, seq_lens,
                 write_blocks, write_offsets):
            return gpt_extend_step(weights, cfg, ccfg, cache, tokens,
                                   block_tables, seq_lens,
                                   write_blocks, write_offsets)

        return self._wrap(body, weights, 5)

    def describe(self) -> str:
        devs = ",".join(str(getattr(d, "id", d))
                        for d in self.mesh.devices.flat)
        b = self.plan.budget()
        return (f"ep={self.ep} axis={self.axis!r} devices=[{devs}] "
                f"experts={self.model_cfg.num_experts} "
                f"a2a_budget={b.get('all_to_all')} "
                f"psum_budget={b.get('psum')}")
