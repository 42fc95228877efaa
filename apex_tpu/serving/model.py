"""Pure-function GPT forward for serving: prefill + paged decode.

The training-side :class:`~apex_tpu.testing.standalone_gpt.GPTModel`
is a flax module built for whole-sequence teacher forcing; serving
needs the same math re-staged around a KV cache: a **prefill** that
runs the prompt once through the existing flash forward kernel while
writing every layer's k/v into the request's pages, and a **decode
step** that advances one token per sequence against the paged cache
through the :func:`~apex_tpu.ops.flash_decode.flash_decode` kernel.

Rather than threading mutable cache collections through flax, the
serving path extracts the model's parameters into a plain pytree
(:class:`GPTServingWeights` — same arrays, no copies beyond unboxing)
and runs an explicit forward whose math mirrors the flax stack
operation-for-operation: fp32 :func:`~apex_tpu.ops.layer_norm.
layer_norm` statistics, ``x @ kernel + bias`` in the model compute
dtype, fp32-softmax attention, gelu MLP, tied LM head.  The serving
tests pin this against ``GPTModel.apply`` so the two stacks cannot
drift.

Everything here is traced code (the engine jits these per bucket) —
shapes are static per call site, per-request dynamics ride data
(block tables, sequence lengths, write slots).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.flash_decode import (eva_attention_reference, eva_flash_decode,
                                flash_decode, flash_decode_multi,
                                paged_attention_multi_reference,
                                paged_attention_reference)
from ..ops.layer_norm import layer_norm
from ..ops.quant_matmul import (QuantGPTServingWeights,
                                QuantLayerWeights, quant_matmul,
                                quantize_weights)
from . import mla_moe, rope_moe
from .kv_cache import (KVCacheConfig, PagedKVCache, plan_page_write,
                       write_prefill_kv, write_token_kv)
from .mla_moe import MlaSpec, init_mla_moe_weights
from .rope_moe import (MOE_TICK_COUNTERS, LayerSpec, RopeMoEWeights,
                       RopeSpec, init_rope_moe_weights)

__all__ = ["GPTServingWeights", "LayerWeights", "MoELayerWeights",
           "ServingModelConfig", "RopeMoEWeights", "LayerSpec",
           "RopeSpec", "init_rope_moe_weights", "MOE_TICK_COUNTERS",
           "MlaSpec", "init_mla_moe_weights", "mtp_prefill_step",
           "mtp_extend_step",
           "QuantGPTServingWeights", "QuantLayerWeights",
           "quantize_weights", "extract_serving_weights",
           "weights_in_compute_dtype",
           "gpt_prefill_step", "gpt_decode_step", "gpt_extend_step",
           "prefill_logits", "decode_logits", "extend_logits",
           "gpt_sequence_logits", "copy_cache_block",
           "gather_cache_blocks", "scatter_cache_blocks"]


class LayerWeights(NamedTuple):
    """One transformer layer's parameters (plain arrays)."""

    ln1_w: jnp.ndarray
    ln1_b: jnp.ndarray
    qkv_k: jnp.ndarray        # (H, 3H)
    qkv_b: jnp.ndarray
    dense_k: jnp.ndarray      # (H, H)
    dense_b: jnp.ndarray
    ln2_w: jnp.ndarray
    ln2_b: jnp.ndarray
    fc1_k: jnp.ndarray        # (H, F)
    fc1_b: jnp.ndarray
    fc2_k: jnp.ndarray        # (F, H)
    fc2_b: jnp.ndarray


class MoELayerWeights(NamedTuple):
    """A transformer layer whose MLP is a Switch-style MoE (ISSUE-19).

    Attention/LN leaves match :class:`LayerWeights`; the dense fc1/fc2
    pair is replaced by a top-1 router and per-expert bias-free FFN
    stacks (the training-side :class:`~apex_tpu.transformer.
    layers_moe.MoEMLP` convention).  The step functions duck-type on
    ``router`` (like Q8 duck-types on the ``*_s`` scale rows), so
    dense and MoE layers mix freely in one model."""

    ln1_w: jnp.ndarray
    ln1_b: jnp.ndarray
    qkv_k: jnp.ndarray        # (H, 3H)
    qkv_b: jnp.ndarray
    dense_k: jnp.ndarray      # (H, H)
    dense_b: jnp.ndarray
    ln2_w: jnp.ndarray
    ln2_b: jnp.ndarray
    router: jnp.ndarray       # (H, E) fp32 — routing is precision-
    wi: jnp.ndarray           # (E, H, F)      # sensitive, stays fp32
    wo: jnp.ndarray           # (E, F, H)


class GPTServingWeights(NamedTuple):
    """The whole model as a pytree of plain arrays."""

    wte: jnp.ndarray          # (V, H) — tied LM head
    wpe: jnp.ndarray          # (S, H)
    layers: Tuple[LayerWeights, ...]
    lnf_w: jnp.ndarray
    lnf_b: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class ServingModelConfig:
    """Static model geometry + serving knobs (hashable — safe to
    close over in jitted builders)."""

    vocab_size: int
    hidden_size: int
    num_heads: int
    num_layers: int
    max_seq: int
    dtype: Any = jnp.float32
    layernorm_eps: float = 1e-5
    # prefill attention: the existing flash fwd kernel, or the dense
    # reference (manual-axis contexts / debugging)
    prefill_flash: bool = True
    # decode attention: 'kernel' = the Pallas flash-decode kernel;
    # 'reference' = the dense gather twin — the naive full-attention
    # baseline the kernel is held against (chip_smoke.py, the tests)
    decode_attention: str = "kernel"
    # tensor-parallel axis name (serving/tp.py): when set, the step
    # functions run PER-SHARD math — heads/ffn columns local, hidden
    # residual global — and the two row-parallel linears (attention
    # dense, MLP fc2) all-reduce their partial sums over this axis
    # before the bias add (the Megatron forward, 2 psums per layer).
    # None (single chip) elides the collectives entirely, so the same
    # programs serve both topologies.
    tp_axis: Optional[str] = None
    # expert-parallel axis name (serving/ep.py): when set, MoE layers
    # (``MoELayerWeights``) run with the global experts sharded over
    # that axis — each rank routes its slice of the replicated token
    # rows, dispatch/return ride the capacity-chunked overlapped
    # all_to_all exchange, and the combined slice replicates through
    # one masked psum per MoE layer.  None runs all experts locally.
    ep_axis: Optional[str] = None
    # MoE geometry/knobs (ignored for all-dense weights): expert count
    # is recorded for context validation/describe (the math reads it
    # off the router leaf), capacity factor sizes the per-rank
    # dispatch buffer, a2a_chunks is the overlap depth (ISSUE-19;
    # 1 = legacy single-shot exchange)
    num_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_a2a_chunks: int = 2
    # head size and cache (key/value) heads, STATED: a model whose
    # heads are not hidden / num_heads wide, or whose query heads share
    # fewer cache heads, says so here.  None takes GPT-2's values,
    # hidden // num_heads and num_heads.
    head_dim: Optional[int] = None
    num_kv_heads: Optional[int] = None
    # 'gpt2': LayerNorm, learned positions, one fused QKV, GELU, tied
    # head (the fields above describe it whole).  'rope_moe'
    # (serving/rope_moe.py): RMSNorm, rotary positions, grouped-query
    # heads with a per-head gate, full and windowed layers mixed, a
    # SwiGLU MLP that is dense or a dropless top-k MoE beside a shared
    # expert, untied head -- one LayerSpec a layer in ``layers``
    # (query heads differ by layer there; ``num_heads`` is unused)
    # 'mla_moe' (serving/mla_moe.py): that block with latent attention
    # (``mla``: its widths; the cache is the latent kind, one row a
    # token: ``num_kv_heads`` 1, ``head_dim`` the stored row's width),
    # sandwich norms, and the model's own multi-token-prediction module
    family: str = "gpt2"
    layers: Tuple[LayerSpec, ...] = ()
    experts_per_token: int = 1
    routed_scaling: float = 1.0
    # the first expert a MoE layer of this chip holds, of the
    # ``num_experts`` its router scores; how many it holds is the
    # expert stacks' own leading dimension (0, and all: one chip)
    expert_first: int = 0
    mla: Optional[MlaSpec] = None
    # MTP modules served as the engine's draft (0 or 1): the cache
    # holds a latent layer for each after the model's own
    mtp_layers: int = 0
    # RMSNorm multiplies by ``1 + w``, its weight held about zero
    norm_unit_offset: bool = False
    # the head is this many vocabularies wide, prediction head ``p`` (the
    # logits ``[p * V, (p + 1) * V)``) for the token ``1 + p`` ahead;
    # head 0 is the next token and the one that is sampled
    pred_heads: int = 1

    def __post_init__(self):
        if self.mla is not None:
            object.__setattr__(self, "head_dim", self.mla.row_dim)
            object.__setattr__(self, "num_kv_heads", 1)
        if self.head_dim is None:
            if self.hidden_size % self.num_heads:
                raise ValueError(
                    f"hidden {self.hidden_size} not divisible by heads "
                    f"{self.num_heads}")
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_heads)
        if self.num_kv_heads is None:
            object.__setattr__(self, "num_kv_heads", self.num_heads)
        if self.family not in ("gpt2", "rope_moe", "mla_moe"):
            raise ValueError(f"family {self.family!r} not in "
                             f"('gpt2', 'rope_moe', 'mla_moe')")
        if (self.family == "mla_moe") != (self.mla is not None):
            raise ValueError("family 'mla_moe', and no other, states "
                             "its latent attention's widths (mla)")
        if self.mtp_layers not in (0, 1) or (self.mtp_layers
                                             and self.mla is None):
            raise ValueError("mtp_layers is 0, or 1 for family 'mla_moe'")
        if self.family != "gpt2":
            if len(self.layers) != self.num_layers:
                raise ValueError(
                    f"family {self.family!r} takes one LayerSpec a "
                    f"layer: {len(self.layers)} given for "
                    f"{self.num_layers}")
            if self.tp_axis is not None or self.ep_axis is not None:
                raise ValueError(
                    f"family {self.family!r} has no tensor- or expert-"
                    f"parallel forward yet")
            for spec in self.layers:
                if spec.num_heads % self.num_kv_heads:
                    raise ValueError(
                        f"{spec.num_heads} query heads do not divide "
                        f"over {self.num_kv_heads} cache heads")
            pooled = {(s.window, s.chunk) for s in self.layers
                      if s.chunk is not None}
            if pooled and (self.family != "rope_moe" or len(pooled) > 1
                           or len(self.layers) != sum(
                               s.chunk is not None for s in self.layers)):
                raise ValueError(
                    "EVA layers (LayerSpec.chunk) are rope_moe layers of "
                    "one window and chunk, and a model of them holds no "
                    "other kind yet: the pooled cache is one pool")
        if self.pred_heads < 1:
            raise ValueError(f"pred_heads {self.pred_heads} must be >= 1")
        if self.decode_attention not in ("kernel", "reference"):
            raise ValueError(
                f"decode_attention {self.decode_attention!r} not in "
                f"('kernel', 'reference')")
        if self.moe_a2a_chunks < 1:
            raise ValueError(
                f"moe_a2a_chunks {self.moe_a2a_chunks} must be >= 1")
        if self.num_experts < 0:
            raise ValueError(
                f"num_experts {self.num_experts} must be >= 0")

    @classmethod
    def from_model(cls, model, **overrides) -> "ServingModelConfig":
        """Geometry from a :class:`~apex_tpu.testing.standalone_gpt.
        GPTModel` instance."""
        heads = model.num_attention_heads
        return cls(vocab_size=model.vocab_size,
                   hidden_size=model.hidden_size,
                   num_heads=heads,
                   num_layers=model.num_layers,
                   max_seq=model.max_sequence_length,
                   dtype=model.dtype,
                   head_dim=model.hidden_size // heads,
                   num_kv_heads=heads, **overrides)


def _unbox(tree):
    import flax.linen as nn

    return jax.tree.map(
        lambda l: l.unbox() if isinstance(l, nn.Partitioned) else l,
        tree, is_leaf=lambda l: isinstance(l, nn.Partitioned))


def extract_serving_weights(params,
                            num_layers: int) -> GPTServingWeights:
    """Flatten a ``GPTModel`` param tree (as returned by ``init`` /
    held by the train loop) into :class:`GPTServingWeights`.  Arrays
    are referenced, not copied, in the dtype the tree has them — a
    freshly trained tree serves without a round-trip through a
    checkpoint.  :class:`~.engine.ServingEngine` makes its own tree of
    these (:func:`weights_in_compute_dtype`: float32 masters under a
    bf16 policy are cast once, when it takes them), so the caller may
    drop this one; a step function handed it directly casts each leaf
    where it reads it."""
    p = _unbox(params)
    emb = p["embedding"]
    tr = p["transformer"]
    layers = []
    for i in range(num_layers):
        lp = tr[f"layer_{i}"]
        attn = lp["self_attention"]
        mlp = lp["mlp"]
        layers.append(LayerWeights(
            ln1_w=lp["input_layernorm"]["weight"],
            ln1_b=lp["input_layernorm"]["bias"],
            qkv_k=attn["query_key_value"]["kernel"],
            qkv_b=attn["query_key_value"]["bias"],
            dense_k=attn["dense"]["kernel"],
            dense_b=attn["dense"]["bias"],
            ln2_w=lp["post_attention_layernorm"]["weight"],
            ln2_b=lp["post_attention_layernorm"]["bias"],
            fc1_k=mlp["dense_h_to_4h"]["kernel"],
            fc1_b=mlp["dense_h_to_4h"]["bias"],
            fc2_k=mlp["dense_4h_to_h"]["kernel"],
            fc2_b=mlp["dense_4h_to_h"]["bias"]))
    return GPTServingWeights(
        wte=emb["word_embeddings"]["embedding"],
        wpe=emb["position_embeddings"]["embedding"],
        layers=tuple(layers),
        lnf_w=tr["final_layernorm"]["weight"],
        lnf_b=tr["final_layernorm"]["bias"])


# The leaves that EVERY use below reads in ``cfg.dtype``: the operands of
# ``_matmul`` (float kernels), the biases ``_linear`` / ``_row_linear``
# add, the expert stacks of ``_moe_mlp`` and the two tables of ``_embed``
# (``wte`` is the tied head of ``_lm_head`` too).  The rest is read as it
# is given: LayerNorm's scale and shift (``layer_norm`` takes its
# statistics and its affine in float32), ``router`` (float32 by design),
# and Q8's int8 kernels beside their float32 scale rows.
_LAYER_COMPUTE_LEAVES = ("qkv_k", "qkv_b", "dense_k", "dense_b")
_COMPUTE_LEAVES = {
    GPTServingWeights: ("wte", "wpe"),
    QuantGPTServingWeights: ("wte", "wpe"),
    LayerWeights: _LAYER_COMPUTE_LEAVES + ("fc1_k", "fc1_b", "fc2_k",
                                           "fc2_b"),
    MoELayerWeights: _LAYER_COMPUTE_LEAVES + ("wi", "wo"),
    QuantLayerWeights: ("qkv_b", "dense_b", "fc1_b", "fc2_b"),
}


def weights_in_compute_dtype(weights, cfg: ServingModelConfig):
    """``weights`` as the steps read them: each leaf of
    ``_COMPUTE_LEAVES`` cast to ``cfg.dtype`` ONCE, so a program that
    takes the tree as arguments finds its matmul operands made (an
    argument cannot be constant-folded: a float32 tree under a bf16
    policy is read whole, and cast, by every decode tick and every
    prefill).  The in-step casts stay and lower to nothing on such a
    tree; the values are the ones they would have made.

    A leaf that has the dtype already is returned ITSELF, and a tree of
    a type not listed (``RopeMoEWeights``: those families are made in
    the compute dtype, float32 where a leaf is read in float32) comes
    back as the object it is, as does a GPT tree with nothing to cast
    (a float32 policy, a tree held before): nothing is copied, which
    matters at 8-10 GB of weights on a 16 GB chip.  The caller's tree
    is not touched."""
    dtype = jnp.dtype(cfg.dtype)

    def cast(node):
        changed = {n: getattr(node, n).astype(dtype)
                   for n in _COMPUTE_LEAVES.get(type(node), ())
                   if getattr(node, n).dtype != dtype}
        return node._replace(**changed) if changed else node

    held = cast(weights)
    layers = tuple(cast(lw) for lw in weights.layers)
    if any(h is not lw for h, lw in zip(layers, weights.layers)):
        held = held._replace(layers=layers)
    return held


def _matmul(x, kernel, dtype, scale):
    """The one matmul both linears share.  ``scale`` is None for a
    dense float kernel (compute-dtype matmul) or the per-output-channel
    fp32 scales of an int8 kernel (Q8: fp32-accumulated
    :func:`~apex_tpu.ops.quant_matmul.quant_matmul`, scale applied
    after the contraction, result cast back to compute dtype — the
    fp32 weight tensor never materializes, APX606's invariant)."""
    if scale is not None:
        return quant_matmul(x, kernel, scale, out_dtype=dtype)
    return x.astype(dtype) @ kernel.astype(dtype)


def _linear(x, kernel, bias, dtype, scale=None):
    """The ColumnParallelLinear single-device math: compute-dtype
    matmul, bias in compute dtype."""
    return _matmul(x, kernel, dtype, scale) + bias.astype(dtype)


def _row_linear(x, kernel, bias, dtype, tp_axis, scale=None):
    """RowParallelLinear: with ``tp_axis`` set the kernel rows are a
    contraction shard, so the partial product all-reduces over the
    axis BEFORE the (replicated) bias adds exactly once; single-chip
    (``tp_axis=None``) is plain ``_linear``.  Per-channel scales
    commute with the shard sum (each shard's partial covers every
    output channel), so Q8 scales apply pre-psum."""
    y = _matmul(x, kernel, dtype, scale)
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y + bias.astype(dtype)


def _moe_mlp(m_in, lw: MoELayerWeights, cfg):
    """Switch-style MoE FFN for serving: top-1 router (greedy serving
    is deterministic — no stochastic second-choice policy), the fused
    routing front (:func:`~apex_tpu.ops.moe_routing.
    moe_route_dispatch`), bias-free expert stacks.

    Single chip (``cfg.ep_axis`` None): every expert is local — route,
    batch the expert einsums over the ``(E, capacity, H)`` buffer,
    gate-combine.  Under ``cfg.ep_axis`` (serving/ep.py) the experts
    are weight-sharded over the axis while tokens/attention/cache stay
    replicated: each rank routes its ``T/n`` slice of the token rows,
    dispatch/return ride the capacity-chunked overlapped all_to_all
    exchange (``cfg.moe_a2a_chunks`` — the ISSUE-19 schedule APX704
    stays quiet on), and the combined slice replicates through ONE
    masked psum per MoE layer, so downstream math (residual, next
    layer, argmax) is shard-invariant exactly like the TP forward's
    post-psum activations.  Buckets whose row count doesn't divide the
    axis fall back to every rank routing the full batch (redundant
    expert FLOPs, weights still sharded — correctness never depends
    on bucket/axis alignment)."""
    from ..transformer.expert_parallel import moe_dispatch_combine_fused

    hdim = m_in.shape[-1]
    x2d = m_in.reshape(-1, hdim)
    t = x2d.shape[0]
    e = lw.router.shape[-1]
    dt = cfg.dtype
    logits = x2d.astype(jnp.float32) @ lw.router.astype(jnp.float32)

    def expert_fn(d):
        # d: (local_experts, rows, H) — the dispatched buffer (or its
        # arrived exchange chunk); wi/wo are the local expert stacks
        h1 = jax.nn.gelu(jnp.einsum(
            "ech,ehf->ecf", d.astype(dt), lw.wi.astype(dt),
            preferred_element_type=jnp.float32))
        return jnp.einsum(
            "ecf,efh->ech", h1.astype(dt), lw.wo.astype(dt),
            preferred_element_type=jnp.float32).astype(dt)

    axis = cfg.ep_axis
    if axis is None or t % _axis_size(axis) != 0:
        y, _ = moe_dispatch_combine_fused(
            x2d.astype(dt), logits, expert_fn, e,
            capacity_factor=cfg.moe_capacity_factor, axis_name=axis,
            a2a_chunks=cfg.moe_a2a_chunks)
        return y.reshape(m_in.shape)
    n = _axis_size(axis)
    tl = t // n
    r = jax.lax.axis_index(axis)
    xs = jax.lax.dynamic_slice_in_dim(x2d, r * tl, tl, axis=0)
    ls = jax.lax.dynamic_slice_in_dim(logits, r * tl, tl, axis=0)
    y_local, _ = moe_dispatch_combine_fused(
        xs.astype(dt), ls, expert_fn, e,
        capacity_factor=cfg.moe_capacity_factor, axis_name=axis,
        a2a_chunks=cfg.moe_a2a_chunks)
    pad = jnp.zeros((t, hdim), y_local.dtype)
    y = jax.lax.psum(
        jax.lax.dynamic_update_slice_in_dim(pad, y_local, r * tl,
                                            axis=0), axis)
    return y.reshape(m_in.shape)


def _axis_size(axis) -> int:
    from .._compat import axis_size

    return axis_size(axis) if axis is not None else 1


def _spec(cfg, i: int) -> Optional[LayerSpec]:
    """Layer ``i``'s :class:`LayerSpec` -- None for a GPT-2 layer, which
    the config's scalar fields describe whole.  What the per-layer
    pieces below dispatch on."""
    return cfg.layers[i] if cfg.layers else None


def _attn_inputs(x, lw, cfg, spec, positions, h, d):
    """One layer's normed input and its q, k, v ``(..., heads, d)``:
    GPT-2's LayerNorm and fused QKV of ``h`` heads each, or
    :mod:`.rope_moe`'s RMSNorm, separate projections of
    ``spec.num_heads`` query and ``h`` cache heads, rotated by
    ``positions``."""
    if spec is not None:
        a_in = rope_moe.rms_norm(x, lw.norm1, cfg.layernorm_eps,
                                 cfg.norm_unit_offset)
        return (a_in,) + rope_moe.qkv(a_in, lw, spec, cfg, positions)
    a_in = layer_norm(x, lw.ln1_w, lw.ln1_b,
                      cfg.layernorm_eps).astype(cfg.dtype)
    qkv = _linear(a_in, lw.qkv_k, lw.qkv_b, cfg.dtype,
                  getattr(lw, "qkv_s", None))
    qkv = qkv.reshape(*x.shape[:-1], h, 3 * d)
    return (a_in,) + tuple(jnp.split(qkv, 3, axis=-1))


def _attn_scope(spec):
    """The device-trace name of a ``rope_moe`` layer's attention, by
    its kind; GPT-2's programs keep the names they have."""
    if spec is None:
        return contextlib.nullcontext()
    return jax.named_scope("apex.attn.window" if spec.window
                           else "apex.attn.full")


def _attn_branch(ctx, a_in, lw, cfg, spec):
    """Attention context ``(..., heads, d)`` -> the block's attention
    branch ``(..., H)``."""
    if spec is not None:
        return rope_moe.attn_out(ctx, a_in, lw, spec, cfg)
    ctx = ctx.reshape(*ctx.shape[:-2], -1)
    return _row_linear(ctx, lw.dense_k, lw.dense_b, cfg.dtype,
                       cfg.tp_axis, getattr(lw, "dense_s", None))


def _eva_prefill_attention(x, lw, cfg, spec, cache_cfg, cache, layer,
                           positions, blocks, start, summary_table,
                           pool_blocks):
    """An EVA layer's attention branch over ONE window-aligned chunk
    (``x`` (1, s_pad, H), its first position ``start`` a multiple of the
    window): ``(cache, branch (1, s_pad, H))``.

    The chunk is its own window, so its keys and values are its own
    activations: they are written to the window's pages (``blocks``) for
    the decode steps that follow, every page of the chunk is pooled
    (``pool_chunks``) into the summary pages it fills (``pool_blocks``,
    ``s_pad / bs^2`` of them; a page the chunk only starts pools
    padding, to a row that is written again when the page completes and
    not seen before), and only the pooled rows of the EARLIER windows
    come from pages: ``summary_table`` (their capacity, dump-padded),
    of which the first ``start / chunk`` rows are there, gathered in
    front of the causal square."""
    from ..ops.flash_attention import flash_attention, mha_reference

    h, d = cache_cfg.num_heads, cache_cfg.head_dim
    bs, scale = cache_cfg.block_size, d ** -0.5
    if spec.chunk != bs or spec.window != cache_cfg.window:
        raise ValueError(
            f"an EVA layer of window {spec.window} and chunk {spec.chunk} "
            f"is served from a pooled cache of that window whose pages "
            f"are a chunk long (window {cache_cfg.window}, block_size "
            f"{bs}): default_cache_config makes it")
    with jax.named_scope("apex.attn.eva"):
        a_in, q, k, v = _attn_inputs(x, lw, cfg, spec, positions, h, d)
        cache = write_prefill_kv(cache, cache_cfg, layer, k[0], v[0],
                                 blocks)
        with jax.named_scope("apex.attn.eva.pool"):
            kp, vp = rope_moe.pool_chunks(
                k[0].reshape(-1, bs, h, d), v[0].reshape(-1, bs, h, d),
                lw, scale)
            cache = write_prefill_kv(
                cache, cache_cfg, layer, kp.astype(cfg.dtype),
                vp.astype(cfg.dtype), pool_blocks)
        kc, vc, _, _ = cache.layer(layer)
        prefix = summary_table.shape[0] * bs

        def with_prefix(arr, own):
            # (P, h, bs, d) summary pages -> (1, h, P * bs, d) rows
            rows = arr[summary_table].transpose(1, 0, 2, 3) \
                .reshape(1, h, prefix, d)
            return jnp.concatenate(
                [rows, own.transpose(0, 2, 1, 3).astype(rows.dtype)], 2)

        at = jnp.arange(prefix + x.shape[1], dtype=jnp.int32)
        there = (at < start // spec.chunk) | (at >= prefix)
        attn = flash_attention if cfg.prefill_flash else mha_reference
        ctx = attn(q.transpose(0, 2, 1, 3), with_prefix(kc, k),
                   with_prefix(vc, v), scale=scale, causal=True,
                   prefix=prefix, kv_mask=there[None])
        return cache, _attn_branch(ctx.transpose(0, 2, 1, 3), a_in, lw,
                                   cfg, spec)


def _eva_decode_attention(x, lw, cfg, spec, cache_cfg, cache, layer,
                          positions, write, pool_write, write_blocks,
                          block_tables, seq_lens):
    """An EVA layer's attention branch for one new position a row
    (``x`` (b, H)): ``(cache, branch (b, H))``.  The position's key and
    value go to its window page; the query reads, under one softmax, the
    pooled rows of every earlier window and its own window's rows up to
    itself (``block_tables``: those summary pages, then the window's;
    the two lengths follow from the position); and where the position
    completed its page (``pool_write`` names a summary row, else the
    dump page) the page just written is pooled to that row."""
    h, d = cache_cfg.num_heads, cache_cfg.head_dim
    scale = d ** -0.5
    with jax.named_scope("apex.attn.eva"):
        a_in, q, k, v = _attn_inputs(x, lw, cfg, spec, positions, h, d)
        cache = write_token_kv(cache, cache_cfg, layer, k, v, write)
        kc, vc, _, _ = cache.layer(layer)
        live = seq_lens > 0
        summary_lens = jnp.where(
            live, positions // spec.window * (spec.window // spec.chunk), 0)
        window_lens = jnp.where(live, positions % spec.window + 1, 0)
        attn = eva_flash_decode if cfg.decode_attention == "kernel" \
            else eva_attention_reference
        ctx = attn(q, kc, vc, block_tables, summary_lens, window_lens,
                   scale=scale)
        with jax.named_scope("apex.attn.eva.pool"):
            kp, vp = rope_moe.pool_chunks(
                kc[write_blocks].transpose(0, 2, 1, 3),
                vc[write_blocks].transpose(0, 2, 1, 3), lw, scale)
            cache = write_token_kv(cache, cache_cfg, layer,
                                   kp.astype(cfg.dtype),
                                   vp.astype(cfg.dtype), pool_write)
        return cache, _attn_branch(ctx, a_in, lw, cfg, spec)


def _eva(spec) -> bool:
    return spec is not None and spec.chunk is not None


def _sampled(logits, cfg):
    """The logits a token is sampled from: the next token's, prediction
    head 0 of a head that is several vocabularies wide."""
    return logits[..., :cfg.vocab_size] if cfg.pred_heads > 1 else logits


def _layer_tail(x, lw, attn_out, cfg, live=None):
    """residual + norm + MLP + residual -- shared by prefill, decode
    and extend; returns ``(x, tick counters or None)``.  GPT-2: fc1 is
    column-split under TP (local gelu), fc2 row-split (the layer's
    second all-reduce); an ``MoELayerWeights`` layer routes through the
    capacity MoE FFN instead (duck-typed on ``router``).  ``rope_moe``
    (duck-typed on ``norm2``): RMSNorm and the dense or dropless-MoE
    SwiGLU, whose routing counts over the ``live`` rows come back for
    the decode tick's telemetry.  A layer with sandwich norms
    (``mla_moe``, duck-typed on ``norm1_post``) norms each branch once
    more before it joins the residual stream."""
    sandwich = hasattr(lw, "norm1_post")
    if sandwich:
        attn_out = rope_moe.rms_norm(attn_out, lw.norm1_post,
                                     cfg.layernorm_eps)
    x = x + attn_out.astype(x.dtype)
    if hasattr(lw, "norm2"):
        branch, counters = rope_moe.mlp(
            rope_moe.rms_norm(x, lw.norm2, cfg.layernorm_eps,
                              cfg.norm_unit_offset), lw, cfg, live)
        if sandwich:
            branch = rope_moe.rms_norm(branch, lw.norm2_post,
                                       cfg.layernorm_eps)
        return x + branch, counters
    m_in = layer_norm(x, lw.ln2_w, lw.ln2_b,
                      cfg.layernorm_eps).astype(cfg.dtype)
    if getattr(lw, "router", None) is not None:
        return x + _moe_mlp(m_in, lw, cfg).astype(x.dtype), None
    h1 = jax.nn.gelu(_linear(m_in, lw.fc1_k, lw.fc1_b, cfg.dtype,
                             getattr(lw, "fc1_s", None)))
    mlp_out = _row_linear(h1, lw.fc2_k, lw.fc2_b, cfg.dtype,
                          cfg.tp_axis, getattr(lw, "fc2_s", None))
    return x + mlp_out.astype(x.dtype), None


def _lm_head(x, weights, cfg):
    """Final norm + output projection: GPT-2's LayerNorm and tied
    embedding (GPTHead + attend), or ``rope_moe``'s RMSNorm and untied
    head with float32 logits."""
    if isinstance(weights, RopeMoEWeights):
        return rope_moe.head_logits(x, weights, cfg.layernorm_eps,
                                    cfg.norm_unit_offset)
    hf = layer_norm(x, weights.lnf_w, weights.lnf_b,
                    cfg.layernorm_eps).astype(cfg.dtype)
    return hf.astype(cfg.dtype) @ weights.wte.astype(cfg.dtype).T


def _embed(weights, tokens, positions, cfg):
    """Token (+ GPT-2's learned position) embeddings: the residual
    stream, which ``rope_moe`` carries in float32."""
    if isinstance(weights, RopeMoEWeights):
        return jnp.take(weights.embed, tokens, axis=0).astype(jnp.float32)
    dtype = cfg.dtype
    return (jnp.take(weights.wte.astype(dtype), tokens, axis=0)
            + jnp.take(weights.wpe.astype(dtype), positions, axis=0))


def gpt_prefill_step(weights, cfg: ServingModelConfig,
                     cache_cfg: KVCacheConfig, cache: PagedKVCache,
                     tokens: jnp.ndarray, length: jnp.ndarray,
                     blocks: jnp.ndarray, *chunk):
    """Run one prompt through the model, writing every layer's k/v
    into the request's pages; returns ``(cache, next_token)``.
    (:func:`prefill_logits` is this step before its argmax.)

    ``tokens`` (s_pad,) int32, right-padded to the prompt-length
    bucket (``s_pad = len(blocks) * block_size``); ``length`` the true
    prompt length (traced — one compile covers the whole bucket);
    ``blocks`` (n_pages,) int32 with dump-page padding past the owned
    tail.  Attention is causal over the padded prompt — padded KEYS
    sit in the causal future of every real query, so the row at
    ``length - 1`` (whose argmax is the first generated token) never
    sees them; their own garbage rows land in pages the masked decode
    reads never weight.  The attention itself is the existing flash
    forward kernel (:func:`~apex_tpu.ops.flash_attention.
    flash_attention`) — prefill is exactly a training forward at
    batch 1 (with grouped heads and, on windowed layers, a causal
    window for the ``rope_moe`` family, whose head runs on the last
    real position's row alone).

    A model of EVA layers (the pooled cache) is prefilled one
    window-aligned chunk a call, and ``chunk`` is the three arguments
    that place it: ``start`` (the chunk's first position, a multiple of
    the window; ``length`` counts from it), ``summary_table`` (the
    summary pages of the windows before it, dump-padded to their
    capacity) and ``pool_blocks`` (the ``s_pad / bs^2`` summary pages
    the chunk's own pages pool to, the dump page for those it does not
    reach): :func:`_eva_prefill_attention`."""
    cache, last = prefill_logits(weights, cfg, cache_cfg, cache, tokens,
                                 length, blocks, *chunk)
    return cache, jnp.argmax(_sampled(last, cfg), axis=-1).astype(jnp.int32)


def prefill_logits(weights, cfg, cache_cfg, cache, tokens, length,
                   blocks, *chunk):
    """:func:`gpt_prefill_step` up to the logits of the last real
    position: ``(cache, (V,) logits)``, every prediction head's."""
    cache, x = _prefill_hidden(weights, cfg, cache_cfg, cache, tokens,
                               blocks, *chunk)
    return cache, _last_logits(x, weights, cfg, length)


def _last_logits(x, weights, cfg, length):
    """The logits of a prefill's last real position, from its final
    residual stream ``x`` (1, s_pad, H)."""
    if cfg.family != "gpt2":
        # the head is vocabulary-wide: run it on the one row that is read
        return _lm_head(jax.lax.dynamic_index_in_dim(
            x[0], length - 1, axis=0, keepdims=False), weights, cfg)
    logits = _lm_head(x, weights, cfg)[0]          # (s_pad, V)
    return jax.lax.dynamic_index_in_dim(logits, length - 1, axis=0,
                                        keepdims=False)


def _prefill_hidden(weights, cfg, cache_cfg, cache, tokens, blocks,
                    start=0, *pooled):
    """The prefill's layers: ``(cache, final residual stream (1, s_pad,
    H))``, before the final norm; ``start`` and ``pooled`` place one
    chunk of a model of EVA layers (:func:`gpt_prefill_step`)."""
    from ..ops.flash_attention import flash_attention, mha_reference

    s_pad = tokens.shape[0]
    # head count comes from the CACHE config: under tensor parallelism
    # (serving/tp.py) each shard owns cfg.num_heads / tp heads and its
    # cache is sized to match — the math below is per-shard math
    h, d = cache_cfg.num_heads, cache_cfg.head_dim
    scale = d ** -0.5
    tokens = tokens[None, :]
    positions = jnp.arange(s_pad, dtype=jnp.int32)[None, :]
    if pooled:
        positions = positions + start
    x = _embed(weights, tokens, positions, cfg)
    for i, lw in enumerate(weights.layers):
        spec = _spec(cfg, i)
        if cfg.mla is not None:
            attn_out, latent = mla_moe.expanded(x, lw, cfg, spec,
                                                positions)
            cache = write_prefill_kv(cache, cache_cfg, i,
                                     latent[0, :, None], None, blocks)
        elif _eva(spec):
            cache, attn_out = _eva_prefill_attention(
                x, lw, cfg, spec, cache_cfg, cache, i, positions, blocks,
                start, *pooled)
        else:
            a_in, q, k, v = _attn_inputs(x, lw, cfg, spec, positions, h, d)
            cache = write_prefill_kv(cache, cache_cfg, i, k[0], v[0],
                                     blocks)
            qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            attn = flash_attention if cfg.prefill_flash else mha_reference
            with _attn_scope(spec):
                ctx = attn(qt, kt, vt, scale=scale, causal=True,
                           window=spec.window if spec else None)
            attn_out = _attn_branch(ctx.transpose(0, 2, 1, 3), a_in, lw,
                                    cfg, spec)
        x, _ = _layer_tail(x, lw, attn_out, cfg)
    return cache, x


def gpt_decode_step(weights, cfg: ServingModelConfig,
                    cache_cfg: KVCacheConfig, cache: PagedKVCache,
                    tokens: jnp.ndarray, positions: jnp.ndarray,
                    block_tables: jnp.ndarray, seq_lens: jnp.ndarray,
                    write_blocks: jnp.ndarray,
                    write_offsets: jnp.ndarray, *pool_slots):
    """Advance every batch row one token against the paged cache;
    returns ``(cache, next_tokens)``.

    Per row ``b``: ``tokens[b]`` is the token at position
    ``positions[b]`` (the previously sampled or last prompt token);
    its k/v is written to ``(write_blocks[b], write_offsets[b])``
    layer by layer *before* that layer's attention, so the token
    attends to itself through the cache; ``seq_lens[b] =
    positions[b] + 1`` bounds the attended span.  Inactive bucket
    rows carry ``seq_lens = 0``, point their writes at the dump page,
    and produce a (discarded) deterministic token.  Greedy argmax
    sampling happens in-graph — the step's only output traffic is the
    cache carry and one int32 per row.  The ``rope_moe`` family appends
    :data:`~.rope_moe.MOE_TICK_COUNTERS` to ``next_tokens`` (two more
    int32, summed over its MoE layers and the live rows), so the
    routing's telemetry rides the tick's one fetch.

    Every row's math touches only that row's pages and lanes, so a
    request's token stream is invariant to bucket shape and admission
    interleave — the continuous-batching determinism the serving
    tests prove.  (:func:`decode_logits` is this step before its
    argmax.)

    A model of EVA layers (the pooled cache) takes two more arrays,
    ``pool_slots`` = ``(pool_blocks, pool_offsets)`` (b,): the summary
    row a row's completed page pools to, the dump page where the new
    position completed none (:func:`_eva_decode_attention`).
    """
    cache, logits, counters = decode_logits(
        weights, cfg, cache_cfg, cache, tokens, positions, block_tables,
        seq_lens, write_blocks, write_offsets, *pool_slots)
    next_tokens = jnp.argmax(_sampled(logits, cfg),
                             axis=-1).astype(jnp.int32)
    if counters is not None:
        next_tokens = jnp.concatenate([next_tokens, counters])
    return cache, next_tokens


def decode_logits(weights, cfg, cache_cfg, cache, tokens, positions,
                  block_tables, seq_lens, write_blocks, write_offsets,
                  *pool_slots):
    """:func:`gpt_decode_step` up to its logits: ``(cache, (b, V)
    logits, the MoE tick counters or None)``."""
    h, d = cache_cfg.num_heads, cache_cfg.head_dim   # per-shard heads
    scale = d ** -0.5
    x = _embed(weights, tokens, positions, cfg)   # (b, H)
    live = seq_lens > 0 if cfg.layers else None    # rows the MoE counts
    counters = None
    write = plan_page_write(write_blocks, write_offsets,
                            cache_cfg.block_size)
    pool_write = plan_page_write(*pool_slots, cache_cfg.block_size) \
        if pool_slots else None
    for i, lw in enumerate(weights.layers):
        spec = _spec(cfg, i)
        if cfg.mla is not None:
            cache, attn_out = mla_moe.absorbed(
                x, lw, cfg, spec, cache_cfg, cache, i, positions, write,
                block_tables, seq_lens)
        elif _eva(spec):
            cache, attn_out = _eva_decode_attention(
                x, lw, cfg, spec, cache_cfg, cache, i, positions, write,
                pool_write, write_blocks, block_tables, seq_lens)
        else:
            a_in, q, k, v = _attn_inputs(x, lw, cfg, spec, positions, h, d)
            cache = write_token_kv(cache, cache_cfg, i, k, v, write)
            kc, vc, ks, vs = cache.layer(i)
            attn = flash_decode if cfg.decode_attention == "kernel" \
                else paged_attention_reference
            with _attn_scope(spec):
                ctx = attn(q, kc, vc, block_tables, seq_lens, scale=scale,
                           k_scale=ks, v_scale=vs,
                           window=spec.window if spec else None)
            attn_out = _attn_branch(ctx, a_in, lw, cfg, spec)
        x, c = _layer_tail(x, lw, attn_out, cfg, live)
        if c is not None:
            counters = c if counters is None else counters + c
    return cache, _lm_head(x, weights, cfg), counters    # (b, V)


def gpt_extend_step(weights, cfg: ServingModelConfig,
                    cache_cfg: KVCacheConfig, cache: PagedKVCache,
                    tokens: jnp.ndarray, block_tables: jnp.ndarray,
                    seq_lens: jnp.ndarray,
                    write_blocks: jnp.ndarray,
                    write_offsets: jnp.ndarray):
    """Advance every batch row by a CHUNK of ``t`` tokens against the
    paged cache — the one program behind speculative verification,
    chunked prefill, and warm-prefix tail prefill; returns
    ``(cache, next_tokens)`` with one argmax token per chunk slot.

    ``tokens`` is (b, t): row ``b``'s chunk occupies the contiguous
    positions ``seq_lens[b] - t .. seq_lens[b] - 1`` (``seq_lens``
    counts every k/v-written token INCLUDING this chunk).  Each
    token's k/v goes to ``(write_blocks[b, j], write_offsets[b, j])``
    layer by layer before that layer's attention — the chunk attends
    to itself through the cache, exactly the decode step's discipline
    — and the per-row causal rule is
    :func:`~apex_tpu.ops.flash_decode.flash_decode_multi`'s.  Chunks
    shorter than ``t`` are FRONT-padded (valid tokens last, so the
    final row is always the newest position): padding rows carry
    negative positions, point their writes at the dump page, and emit
    a discarded deterministic token.  ``next_tokens[b, -1]`` after the
    chunk that completes a prompt is the request's first generated
    token; ``next_tokens[b, j]`` under verification is the target
    model's greedy choice after consuming position ``seq_lens[b] - t
    + j`` — the acceptance comparator.

    One compile per (batch bucket, t bucket, pages bucket) — the
    chunk/verify dimensions the engine's warmup adds to the ladder
    product.  (:func:`extend_logits` is this step before its
    argmax.)"""
    cache, logits = extend_logits(
        weights, cfg, cache_cfg, cache, tokens, block_tables, seq_lens,
        write_blocks, write_offsets)
    return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def extend_logits(weights, cfg, cache_cfg, cache, tokens, block_tables,
                  seq_lens, write_blocks, write_offsets):
    """:func:`gpt_extend_step` up to its logits: ``(cache, (b, t, V)
    logits)``."""
    cache, x, _ = _extend_hidden(weights, cfg, cache_cfg, cache, tokens,
                                 block_tables, seq_lens, write_blocks,
                                 write_offsets)
    return cache, _lm_head(x, weights, cfg)        # (b, t, V)


def _extend_hidden(weights, cfg, cache_cfg, cache, tokens, block_tables,
                   seq_lens, write_blocks, write_offsets):
    """The extend step's layers: ``(cache, final residual stream (b, t,
    H), (positions (b, t), the step's page write))``."""
    h, d = cache_cfg.num_heads, cache_cfg.head_dim   # per-shard heads
    b, t = tokens.shape
    scale = d ** -0.5
    pos = seq_lens.astype(jnp.int32)[:, None] - t \
        + jnp.arange(t, dtype=jnp.int32)[None, :]       # (b, t)
    # padding rows sit at negative positions: clamp the embedding
    # lookup (their output is discarded; attention masks them to 0)
    x = _embed(weights, tokens, jnp.maximum(pos, 0), cfg)  # (b, t, H)
    # the chunk is planned as (b, t): several of a row's tokens land on
    # one page, and the page write puts them in together
    write = plan_page_write(write_blocks, write_offsets,
                            cache_cfg.block_size)
    if any(map(_eva, cfg.layers)):
        raise NotImplementedError(
            "EVA layers have no multi-token extend step: a chunk of a "
            "prompt is a window of its own (gpt_prefill_step), and "
            "speculative verification does not serve the pooled cache")
    for i, lw in enumerate(weights.layers):
        spec = _spec(cfg, i)
        if cfg.mla is not None:
            cache, attn_out = mla_moe.absorbed(
                x, lw, cfg, spec, cache_cfg, cache, i, pos, write,
                block_tables, seq_lens)
        else:
            a_in, q, k, v = _attn_inputs(x, lw, cfg, spec, pos, h, d)
            cache = write_token_kv(cache, cache_cfg, i, k, v, write)
            kc, vc, ks, vs = cache.layer(i)
            attn = flash_decode_multi \
                if cfg.decode_attention == "kernel" \
                else paged_attention_multi_reference
            with _attn_scope(spec):
                ctx = attn(q, kc, vc, block_tables, seq_lens, scale=scale,
                           k_scale=ks, v_scale=vs,
                           window=spec.window if spec else None)
            attn_out = _attn_branch(ctx, a_in, lw, cfg, spec)
        x, _ = _layer_tail(x, lw, attn_out, cfg)
    return cache, x, (pos, write)


# --- the model's own draft: its multi-token-prediction module ---------------

def _argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def mtp_prefill_step(weights, cfg, cache_cfg, cache, tokens, length,
                     blocks):
    """:func:`gpt_prefill_step` and, in the same program, the MTP module
    over the same positions: ``(cache, [first token, first draft])``.

    The module's input at position ``j`` is the model's final hidden
    state there and the token AFTER it: the prompt's next token, and at
    the last real position the token the prefill just chose.  Its latent
    layer is the cache's last, written through the prompt's own pages;
    its logits at the last real position are for the token two past it:
    the draft of the first decode tick."""
    cache, x = _prefill_hidden(weights, cfg, cache_cfg, cache, tokens,
                               blocks)
    first = _argmax(_last_logits(x, weights, cfg, length))
    eps, spec = cfg.layernorm_eps, cfg.layers[-1]
    at = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    nxt = jnp.where(at == length - 1, first, jnp.roll(tokens, -1))
    with jax.named_scope("apex.mtp"):
        g = mla_moe.mtp_input(x, nxt[None], weights, eps)
        lw = weights.mtp.layer
        attn_out, latent = mla_moe.expanded(g, lw, cfg, spec, at[None])
        cache = write_prefill_kv(cache, cache_cfg, cfg.num_layers,
                                 latent[0, :, None], None, blocks)
        g, _ = _layer_tail(g, lw, attn_out, cfg)
        draft = _argmax(mla_moe.mtp_logits(jax.lax.dynamic_index_in_dim(
            g[0], length - 1, axis=0, keepdims=False), weights, eps))
    return cache, jnp.stack([first, draft])


def mtp_extend_step(weights, cfg, cache_cfg, cache, tokens, block_tables,
                    seq_lens, write_blocks, write_offsets):
    """:func:`gpt_extend_step` as speculation's verify step, and in the
    same program the MTP module on the hidden states it ends in:
    ``(cache, (b, 2t))``, the target's greedy token after each of the
    ``t`` slots, then the module's draft of the token after THAT.

    Slot ``j``'s hidden state is the model's at its position given the
    tokens fed up to it, so slot ``j``'s draft stands if the fed tokens
    up to ``j`` were accepted: the engine takes the draft of the last
    slot it kept.  The module's latents go to the cache's last layer
    through the same write slots, and a rejected slot's is overwritten
    by the next tick's before anything attends to it, as the model's
    own is."""
    cache, x, (pos, write) = _extend_hidden(
        weights, cfg, cache_cfg, cache, tokens, block_tables, seq_lens,
        write_blocks, write_offsets)
    chosen = _argmax(_lm_head(x, weights, cfg))          # (b, t)
    eps = cfg.layernorm_eps
    with jax.named_scope("apex.mtp"):
        g = mla_moe.mtp_input(x, chosen, weights, eps)
        lw = weights.mtp.layer
        cache, attn_out = mla_moe.absorbed(
            g, lw, cfg, cfg.layers[-1], cache_cfg, cache, cfg.num_layers,
            pos, write, block_tables, seq_lens)
        g, _ = _layer_tail(g, lw, attn_out, cfg)
        drafts = _argmax(mla_moe.mtp_logits(g, weights, eps))
    return cache, jnp.concatenate([chosen, drafts], axis=1)


def gpt_sequence_logits(weights, cfg: ServingModelConfig,
                        tokens: jnp.ndarray) -> jnp.ndarray:
    """Whole-sequence teacher-forced logits ``(b, s, V)`` — no KV
    cache, no paging: the training-forward view of the SAME serving
    math (same per-layer pieces, so Q8 weights run the quantized
    matmuls here too).  This is the oracle behind the
    bench's perplexity-delta row and the Q8-vs-O5 divergence tests;
    single-chip only (head counts come from ``cfg``, not a sharded
    cache config)."""
    from ..ops.flash_attention import flash_attention, mha_reference

    b, s = tokens.shape
    h, d = cfg.num_kv_heads, cfg.head_dim
    scale = d ** -0.5
    if any(map(_eva, cfg.layers)):
        raise NotImplementedError(
            "no cache-free whole-sequence forward of EVA layers here: "
            "benchmarks/reference_evabyte.py is that oracle")
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :],
                           (b, s))
    x = _embed(weights, tokens, pos, cfg)
    for i, lw in enumerate(weights.layers):
        spec = _spec(cfg, i)
        if cfg.mla is not None:
            attn_out, _ = mla_moe.expanded(x, lw, cfg, spec, pos)
        else:
            a_in, q, k, v = _attn_inputs(x, lw, cfg, spec, pos, h, d)
            qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            attn = flash_attention if cfg.prefill_flash else mha_reference
            ctx = attn(qt, kt, vt, scale=scale, causal=True,
                       window=spec.window if spec else None)
            attn_out = _attn_branch(ctx.transpose(0, 2, 1, 3), a_in, lw,
                                    cfg, spec)
        x, _ = _layer_tail(x, lw, attn_out, cfg)
    return _lm_head(x, weights, cfg)


def copy_cache_block(cache: PagedKVCache, src: jnp.ndarray,
                     dst: jnp.ndarray) -> PagedKVCache:
    """Device-side copy-on-write: duplicate block ``src`` (all layers,
    k+v+scales) into block ``dst``.  Traced code — the engine jits it
    once per cache (src/dst ride as data, so every CoW reuses the one
    compiled program) with the cache donated, making the copy an
    in-place page-sized DMA a leaf."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return jax.tree.map(lambda a: a.at[dst].set(a[src]), cache)


def gather_cache_blocks(cache: PagedKVCache, blocks: jnp.ndarray):
    """Pull ``blocks`` (n,) int32 out of the paged cache as one
    contiguous payload — the EXPORT half of the disaggregated
    prefill→decode KV handoff (serving/fleet.py).  Returns
    ``(k, v, k_scale, v_scale)`` with ``k``/``v`` shaped
    ``(L, n, hk, bs, dk)``: the layers' page spans stacked, the wire
    format (storage bytes untouched — an int8 cache ships int8 rows +
    their fp32 scales, a bf16 cache ships bf16) and scales
    ``(L, n, h, bs)`` or None.  Traced code:
    the fleet jits it with the block list as data, padded to a page
    rung, so every export of a rung-sized span reuses one compiled
    program (dump-page padding gathers harmless zeros the importer
    drops)."""
    blocks = jnp.asarray(blocks, jnp.int32)
    return tuple(None if leaves is None
                 else jnp.stack([a[blocks] for a in leaves])
                 for leaves in cache)


def scatter_cache_blocks(cache: PagedKVCache, k: jnp.ndarray,
                         v: jnp.ndarray, k_scale, v_scale,
                         blocks: jnp.ndarray) -> PagedKVCache:
    """Write an exported payload into ``blocks`` of this cache — the
    IMPORT half of the KV handoff: layer ``i`` of the stacked payload
    goes to layer ``i``'s arrays.  Shapes/dtypes must match this
    cache's storage layout exactly (the fleet validates the two
    replicas' :class:`~.kv_cache.KVCacheConfig` geometry before any
    transfer); the cache is donated by the jitted caller so the
    scatter is an in-place page-span DMA.  Padding entries pointing at
    the dump block overwrite only the dump page (never read
    unmasked)."""
    blocks = jnp.asarray(blocks, jnp.int32)
    return PagedKVCache(*(
        None if leaves is None
        else tuple(a.at[blocks].set(x[i]) for i, a in enumerate(leaves))
        for leaves, x in zip(cache, (k, v, k_scale, v_scale))))
