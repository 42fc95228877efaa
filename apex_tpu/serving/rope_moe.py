"""The serving model's second family, ``rope_moe``: the pieces of a
pre-RMSNorm decoder block with rotary positions, grouped-query heads,
a per-head output gate, causal attention that is full on some layers
and windowed on others, and a SwiGLU MLP that is dense on some layers
and, on the others, a **dropless** top-k mixture of experts beside a
shared expert.  No biases anywhere, an untied output head.

:mod:`.model` owns the three step functions (prefill, decode, extend)
for both families; what differs between the GPT-2 block and this one is
a handful of per-layer pieces, and this module holds this family's:
the static per-layer description (:class:`LayerSpec`, carried by
``ServingModelConfig.layers``), the weights (:class:`RopeMoEWeights`,
made from a PRNG key by :func:`init_rope_moe_weights`; there is no
training model to extract them from yet), and the functions the steps
call between the cache writes and the attention kernels they share with
GPT-2.

Precision: matrices are held and multiplied in the model dtype (bf16)
with float32 accumulation; the residual stream, the norms' statistics,
the rotary tables, the gates, the router (weights, scores, top-k and the
combine weights) and the logits are float32.

Routing has no capacity and drops no token: the (row, expert) pairs are
sorted by expert and pushed through ``jax.lax.ragged_dot``, which the
TPU compiler lowers to a grouped matmul (static shapes, exactly ``k``
experts' work a row and only the experts hit read, at a decode batch's
rows as at a prefill's).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["RopeSpec", "LayerSpec", "RopeMoELayerWeights",
           "RopeMoEWeights", "init_rope_moe_weights",
           "MOE_TICK_COUNTERS", "rope_inv_freq", "pool_chunks"]

# What a decode step of this family appends to its ``next_tokens``
# (int32, summed over the MoE layers, live rows only): the distinct
# experts that received a row, the most rows one expert received and,
# from a layer that holds a share of its experts alone, the routed
# (row, expert) pairs that fell on the experts it holds.  Experts are
# counted where they are held: an expert on another chip is not hit here.
MOE_TICK_COUNTERS = ("experts_hit", "expert_max_rows", "pairs_held")


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """Rotary embedding of one kind of layer: the first ``rotary_dim``
    of a head's dims rotate, dim ``i`` paired with ``i + rotary_dim/2``.
    ``yarn_factor`` set: YaRN's per-frequency blend of the plain and the
    ``1/factor`` frequencies, with cos and sin scaled by
    ``attention_factor``."""

    theta: float
    rotary_dim: int
    yarn_factor: Optional[float] = None
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer is, beyond the shapes of its weights."""

    num_heads: int                 # query heads (cache heads: the config)
    window: Optional[int]          # None: full causal attention
    rope: RopeSpec
    moe: bool                      # False: a dense SwiGLU MLP
    # an EVA layer (``chunk`` set): ``window`` is ALIGNED, not sliding --
    # position t sees the positions <= t of its own window
    # floor(t / window) exactly and, of every earlier window, one pooled
    # (key, value) a ``chunk`` of positions (:func:`pool_chunks`); no
    # output gate.  Served from the pooled cache (``KVCacheConfig.window``),
    # whose pages are a chunk long.
    chunk: Optional[int] = None


class RopeMoELayerWeights(NamedTuple):
    """One layer.  A dense layer holds ``w1/w3/w2`` and None for the
    expert leaves; a MoE layer the reverse."""

    norm1: jnp.ndarray             # (H,) fp32
    wq: jnp.ndarray                # (H, heads * d)
    wk: jnp.ndarray                # (H, kv_heads * d)
    wv: jnp.ndarray                # (H, kv_heads * d)
    wg: jnp.ndarray                # (H, heads): per-head output gate
    wo: jnp.ndarray                # (heads * d, H)
    norm2: jnp.ndarray             # (H,) fp32
    w1: Optional[jnp.ndarray]      # (H, F) gate projection
    w3: Optional[jnp.ndarray]      # (H, F) up projection
    w2: Optional[jnp.ndarray]      # (F, H)
    router: Optional[jnp.ndarray]  # (H, E) fp32
    e1: Optional[jnp.ndarray]      # (E, H, Fe)
    e3: Optional[jnp.ndarray]      # (E, H, Fe)
    e2: Optional[jnp.ndarray]      # (E, Fe, H)
    s1: Optional[jnp.ndarray]      # (H, Fs) the shared expert
    s3: Optional[jnp.ndarray]
    s2: Optional[jnp.ndarray]      # (Fs, H)
    # an EVA layer's two learned vectors a head (and no ``wg``)
    phi: Optional[jnp.ndarray] = None   # (kv_heads, d) fp32: pooling query
    mu: Optional[jnp.ndarray] = None    # (kv_heads, d) fp32: pooled-key bias


class RopeMoEWeights(NamedTuple):
    """The whole model as a pytree of plain arrays."""

    embed: jnp.ndarray             # (V, H)
    layers: Tuple[NamedTuple, ...]  # this family's layers or mla_moe's
    norm_f: jnp.ndarray            # (H,) fp32
    head: jnp.ndarray              # (H, V), not tied to ``embed``
    mtp: Optional[NamedTuple] = None   # mla_moe.MtpWeights, where served


def init_rope_moe_weights(key, cfg, *, dense_ffn: int, expert_ffn: int = 0,
                          shared_ffn: int = 0,
                          std: float = 0.02) -> RopeMoEWeights:
    """Seeded random weights for ``cfg`` (a ``rope_moe``
    ``ServingModelConfig``), every leaf made on the device in ONE
    jitted call: matrices normal(0, ``std``) in ``cfg.dtype``, the
    router normal(0, ``std``) in float32, norm weights 1 + normal(0,
    0.1) in float32 (not all-ones, so that a norm weight left out
    shows; normal(0, 0.1) about the offset where the norm adds the 1
    itself, ``cfg.norm_unit_offset``).  An EVA layer's ``phi`` and
    ``mu`` are normal(0, 1) in float32, so that its pooling weights are
    far from uniform and a wrong pooling moves the logits; the head is
    ``cfg.pred_heads`` vocabularies wide."""
    if cfg.family != "rope_moe":
        raise ValueError(f"init_rope_moe_weights: family {cfg.family!r}")
    hidden, d = cfg.hidden_size, cfg.head_dim
    kv, e = cfg.num_kv_heads, cfg.num_experts

    def make(key):
        keys = map(functools.partial(jax.random.fold_in, key),
                   itertools.count(1))          # one a leaf, in order

        def mat(*shape, dtype=cfg.dtype):
            return (std * jax.random.normal(next(keys), shape,
                                            jnp.float32)).astype(dtype)

        def norm():
            return (0.0 if cfg.norm_unit_offset else 1.0) \
                + 0.1 * jax.random.normal(next(keys), (hidden,), jnp.float32)

        layers = []
        for spec in cfg.layers:
            h = spec.num_heads
            eva = spec.chunk is not None
            attn = dict(norm1=norm(), wq=mat(hidden, h * d),
                        wk=mat(hidden, kv * d), wv=mat(hidden, kv * d),
                        wg=None if eva else mat(hidden, h),
                        wo=mat(h * d, hidden), norm2=norm())
            if eva:
                attn.update(
                    phi=jax.random.normal(next(keys), (kv, d), jnp.float32),
                    mu=jax.random.normal(next(keys), (kv, d), jnp.float32))
            none = dict.fromkeys(RopeMoELayerWeights._fields)
            if spec.moe:
                mlp = dict(router=mat(hidden, e, dtype=jnp.float32),
                           e1=mat(e, hidden, expert_ffn),
                           e3=mat(e, hidden, expert_ffn),
                           e2=mat(e, expert_ffn, hidden),
                           s1=mat(hidden, shared_ffn),
                           s3=mat(hidden, shared_ffn),
                           s2=mat(shared_ffn, hidden))
            else:
                mlp = dict(w1=mat(hidden, dense_ffn),
                           w3=mat(hidden, dense_ffn),
                           w2=mat(dense_ffn, hidden))
            layers.append(RopeMoELayerWeights(**{**none, **attn, **mlp}))
        return RopeMoEWeights(embed=mat(cfg.vocab_size, hidden),
                              layers=tuple(layers), norm_f=norm(),
                              head=mat(hidden,
                                       cfg.pred_heads * cfg.vocab_size))

    return jax.jit(make)(key)


# --- the block's pieces ------------------------------------------------------

def _mm(x, w):
    """``x @ w`` in the weight's dtype, accumulated in float32."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def rms_norm(x, w, eps, unit_offset: bool = False):
    """``x / sqrt(mean(x^2) + eps) * w`` in float32; ``unit_offset``:
    times ``1 + w``, the weight held about zero."""
    x = x.astype(jnp.float32)
    if unit_offset:
        w = 1.0 + w
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_inv_freq(rope: RopeSpec) -> jnp.ndarray:
    """(rotary_dim / 2,) float32 angular frequencies.  Under YaRN each
    is a blend of the plain frequency and that over ``yarn_factor``: a
    linear ramp from all-plain at the dim whose wavelength makes
    ``beta_fast`` turns in ``original_max_position`` positions to
    all-interpolated at the one that makes ``beta_slow`` (the dims
    rounded outward to whole numbers)."""
    dim = rope.rotary_dim
    inv = rope.theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rope.yarn_factor is None:
        return inv

    def turns_dim(turns):
        return dim * math.log(rope.original_max_position
                              / (turns * 2 * math.pi)) \
            / (2 * math.log(rope.theta))

    low = max(math.floor(turns_dim(rope.beta_fast)), 0)
    high = min(math.ceil(turns_dim(rope.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inv / rope.yarn_factor * ramp + inv * (1.0 - ramp)


def rope_tables(positions, rope: RopeSpec):
    """(cos, sin), each ``positions.shape + (1, rotary_dim / 2)``
    float32 and already times ``attention_factor``."""
    ang = positions.astype(jnp.float32)[..., None, None] \
        * rope_inv_freq(rope)
    return (jnp.cos(ang) * rope.attention_factor,
            jnp.sin(ang) * rope.attention_factor)


def apply_rope(x, positions, rope: RopeSpec, tables=None):
    """Rotate the first ``rotary_dim`` dims of ``x`` (..., heads, d) by
    its ``positions`` (...,); float32 in, float32 out."""
    rot = rope.rotary_dim
    cos, sin = tables or rope_tables(positions, rope)
    x1, x2, rest = (x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:])
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def qkv(a_in, lw: RopeMoELayerWeights, spec: LayerSpec, cfg, positions):
    """Normed input (..., H) -> rotated q (..., heads, d) and k, plain
    v (..., kv_heads, d), in the model dtype."""
    d, kv = cfg.head_dim, cfg.num_kv_heads
    lead = a_in.shape[:-1]
    tables = rope_tables(positions, spec.rope)
    q = _mm(a_in, lw.wq).reshape(*lead, spec.num_heads, d)
    k = _mm(a_in, lw.wk).reshape(*lead, kv, d)
    v = _mm(a_in, lw.wv).reshape(*lead, kv, d)
    q = apply_rope(q, positions, spec.rope, tables)
    k = apply_rope(k, positions, spec.rope, tables)
    return tuple(t.astype(cfg.dtype) for t in (q, k, v))


def attn_out(ctx, a_in, lw: RopeMoELayerWeights, spec: LayerSpec, cfg):
    """Attention context (..., heads, d) -> the block's attention
    branch (..., H): each head scaled by its sigmoid gate (from the
    normed input; an EVA layer has none), then the output projection."""
    if lw.wg is not None:
        gate = jax.nn.sigmoid(_mm(a_in, lw.wg))            # (..., heads)
        ctx = ctx.astype(jnp.float32) * gate[..., None]
    lead = ctx.shape[:-2]
    return _mm(ctx.reshape(*lead, spec.num_heads * cfg.head_dim), lw.wo)


def head_logits(x, weights: RopeMoEWeights, eps, unit_offset: bool = False):
    """Final RMSNorm and the untied head: float32 logits."""
    return _mm(rms_norm(x, weights.norm_f, eps, unit_offset), weights.head)


def pool_chunks(k, v, lw: RopeMoELayerWeights, scale: float):
    """EVA's chunk pooling: ``k``, ``v`` (..., chunk, heads, d), the
    rotated keys and the values of whole chunks, to one pooled key and
    value (..., heads, d) a chunk, float32.  With the head's learned
    ``phi`` and ``mu``: ``a_j = softmax_j(scale * k_j . phi)`` over the
    chunk's positions, ``k~ = sum_j a_j k_j + mu``, ``v~ = sum_j a_j
    v_j``."""
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    a = jax.nn.softmax(scale * jnp.sum(k * lw.phi, -1), axis=-2)[..., None]
    return jnp.sum(a * k, -3) + lw.mu, jnp.sum(a * v, -3)


def _swiglu(m, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(m, w1)) * _mm(m, w3), w2)


def route(m, router, k: int, scaling: float):
    """(T, H) float32 -> (combine weights (T, k) float32, expert ids
    (T, k)): sigmoid scores, the ``k`` largest, normalised over those
    ``k`` and scaled.  Float32 at full matmul precision: a bf16 pass
    here would swap near-tied experts."""
    scores = jax.nn.sigmoid(jnp.dot(
        m, router, precision=jax.lax.Precision.HIGHEST))
    top, ids = jax.lax.top_k(scores, k)
    return scaling * top / top.sum(-1, keepdims=True), ids


def _experts_sorted(m, lw, weights, ids, first: int = 0):
    """(row, expert) pairs sorted by expert through grouped matmuls:
    exactly ``k`` experts' work a row, whatever the routing.

    The layer holds the experts ``first .. first + e1.shape[0]`` of the
    ``num_experts`` that ``ids`` range over (all of them: Laguna; a
    sixteenth: a chip of an expert-parallel deployment).  Pairs routed
    to an expert held elsewhere are that chip's work: they sort behind
    the held ones and are not computed.  The held pairs are taken a
    static number of rows at a time (:func:`_held_rows`: once, unless
    the routing is skewed towards this chip), so no pair is ever
    dropped and an even routing pays for its own share alone."""
    t, k = ids.shape
    held = lw.e1.shape[0]
    flat = ids.reshape(t * k)
    everything = (first, held) == (0, lw.router.shape[-1])
    if not everything:
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < held), flat, held)
    order = jnp.argsort(flat)          # pairs held elsewhere sort last
    sizes = jnp.zeros((held + (not everything),), jnp.int32) \
        .at[flat].add(1)[:held]

    def run(order, sizes, valid=None):
        xs = m.astype(lw.e1.dtype)[order // k]             # (rows, H)
        gate = jax.lax.ragged_dot(xs, lw.e1, sizes,
                                  preferred_element_type=jnp.float32)
        up = jax.lax.ragged_dot(xs, lw.e3, sizes,
                                preferred_element_type=jnp.float32)
        act = jax.nn.silu(gate) * up \
            * weights.reshape(t * k)[order][:, None]
        out = jax.lax.ragged_dot(act.astype(lw.e2.dtype), lw.e2, sizes,
                                 preferred_element_type=jnp.float32)
        if valid is not None:      # rows past the groups hold no result
            out = jnp.where(valid[:, None], out, 0.0)
        return jnp.zeros((t, m.shape[-1]), jnp.float32) \
            .at[order // k].add(out)

    if everything:
        return run(order, sizes)
    rows = _held_rows(t * k, held, lw.router.shape[-1])
    ends = jnp.cumsum(sizes)
    order = jnp.pad(order, (0, -(t * k) % rows))

    def some(i, total):
        lo = i * rows
        at = lo + jnp.arange(rows, dtype=jnp.int32)
        return total + run(
            jax.lax.dynamic_slice_in_dim(order, lo, rows),
            jnp.clip(ends, lo, lo + rows)
            - jnp.clip(ends - sizes, lo, lo + rows), at < ends[-1])

    return jax.lax.fori_loop(
        0, (ends[-1] + rows - 1) // rows, some,
        jnp.zeros((t, m.shape[-1]), jnp.float32))


def _held_rows(pairs: int, held: int, num_experts: int) -> int:
    """The sorted pairs a layer that holds ``held`` of ``num_experts``
    pushes through its grouped matmuls at a time: twice its even share
    of ``pairs``, in whole sublane tiles.  The grouped matmul's time
    grows with the rows it is given, live or not (a 2,048-token prefill
    on a chip that holds a sixteenth: 1,024 pairs expected, standard
    deviation 31), so the bound is kept near the share and the rare
    step that passes it takes a second go."""
    return min(pairs, -(-2 * pairs * held // num_experts // 8) * 8)


def moe_counters(ids, live, num_experts: int, first: int = 0,
                 held: Optional[int] = None):
    """:data:`MOE_TICK_COUNTERS` of one layer as int32s, over the rows
    where ``live`` (T,) holds: two where the layer holds every expert,
    the pairs that fell on its own as a third where it holds ``held``
    from ``first``."""
    if held is None or (first, held) == (0, num_experts):
        rows = jnp.zeros((num_experts,), jnp.int32).at[
            ids.reshape(-1)].add(
                jnp.repeat(live.astype(jnp.int32), ids.shape[1]))
        return jnp.stack([(rows > 0).sum().astype(jnp.int32), rows.max()])
    local = ids.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    rows = jnp.zeros((held + 1,), jnp.int32).at[local].add(
        jnp.repeat(live.astype(jnp.int32), ids.shape[1]))[:held]
    return jnp.stack([(rows > 0).sum().astype(jnp.int32), rows.max(),
                      rows.sum()])


def mlp(m, lw, cfg, live=None):
    """The block's MLP branch on normed input (..., H) float32:
    ``(branch (..., H) float32, tick counters or None)``.  A MoE layer
    routes over every expert the router knows and computes the part of
    those it holds (``cfg.expert_first`` on, as many as ``lw.e1`` has);
    the shared expert is whole on every chip."""
    if lw.router is None:
        return _swiglu(m, lw.w1, lw.w3, lw.w2), None
    lead, hidden = m.shape[:-1], m.shape[-1]
    m2 = m.reshape(-1, hidden)
    with jax.named_scope("apex.moe.route"):
        weights, ids = route(m2, lw.router, cfg.experts_per_token,
                             cfg.routed_scaling)
        counters = None if live is None else moe_counters(
            ids, live.reshape(-1), lw.router.shape[-1], cfg.expert_first,
            lw.e1.shape[0])
    with jax.named_scope("apex.moe.experts"):
        routed = _experts_sorted(m2, lw, weights, ids, cfg.expert_first)
    with jax.named_scope("apex.moe.shared"):
        shared = _swiglu(m2, lw.s1, lw.s3, lw.s2)
    return (routed + shared).reshape(*lead, hidden), counters
