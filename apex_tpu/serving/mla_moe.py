"""The serving model's third family, ``mla_moe``: a decoder block with
**multi-head latent attention** (MLA) over a paged latent cache,
**sandwich norms** (a norm before AND after each branch), and
:mod:`.rope_moe`'s MLP -- dense SwiGLU on the leading layers, then a
dropless top-k mixture of sigmoid-routed experts beside a shared expert,
of which a chip may hold a share -- plus the model's own
**multi-token-prediction** (MTP) module, which the engine can serve as
its draft.

What differs from :mod:`.rope_moe` is attention, the two extra norms
(which :func:`.model._layer_tail` applies where a layer has them) and
the MTP module; the router, the experts, the norms' and the rotary
arithmetic, the head and the weights' container are that module's.

MLA, per token ``x = N_in(h)``: a low-rank query ``c_q = N_q(x W_dq)``,
``[q_nope ; q_rope]_i = c_q W_uq`` for each head ``i``; one **latent**
for all heads, ``[c_kv ; k_r] = x W_dkv`` with ``c_kv <- N_kv(c_kv)``
and ``k_rope = RoPE(k_r)``, which is all the cache holds; keys and
values are ``[k_nope_i ; v_i] = c_kv W_ukv``.  Two forms of one
equation:

* **expanded** (:func:`expanded`: prefill, whole sequences): keys and
  values are made from the latent, heads score on ``nope + rope`` dims
  and carry ``v`` dims through the flash forward kernel;
* **absorbed** (:func:`absorbed`: decode, verify, chunked extend):
  ``W_uk`` moves to the query's side (``q~_i = q_nope_i W_uk_i^T``
  scores against ``c_kv`` itself) and ``W_uv`` behind the softmax
  (``o_i = (a_i c_kv) W_uv_i``), so the paged kernel
  (:func:`~apex_tpu.ops.latent_decode.latent_decode`) reads the latent
  as it lies in the cache and nothing is expanded per cached token.

``W_ukv`` is held as its two halves, ``w_uk`` (heads, nope, rank) and
``w_uv`` (heads, rank, v): each form reads the half it needs as it
lies, and no copy or slice of the other is made.  Both forms read the
latent rounded to the cache's dtype, so they agree to the rounding of
their matmuls.

Precision as :mod:`.rope_moe`: matrices in the model dtype with float32
accumulation; residual stream, norms, rotary tables, router and logits
float32.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops.latent_decode import (latent_attention_multi_reference,
                                 latent_attention_reference,
                                 latent_decode, latent_decode_multi)
from .kv_cache import write_token_kv
from .rope_moe import (RopeMoEWeights, _mm, apply_rope, rms_norm,
                       rope_tables)

__all__ = ["MlaSpec", "MlaMoELayerWeights", "MtpWeights",
           "init_mla_moe_weights"]


@dataclasses.dataclass(frozen=True)
class MlaSpec:
    """The widths of latent attention (the published keys
    ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``)."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int

    @property
    def latent_dim(self) -> int:
        """A cached token's row: ``[c_kv ; k_rope]``."""
        return self.kv_rank + self.rope_dim

    @property
    def row_dim(self) -> int:
        """The width a latent row is STORED at: ``latent_dim`` filled
        with zeros to whole 128-lane tiles (576 -> 640).  The TPU tiles
        an array's last dimension to 128 lanes either way, and where it
        is no multiple of them the compiler lays the cache out with its
        block axis innermost instead and copies all of it to the
        kernel's layout and back on every step; the zeros score 0
        against a query filled alike."""
        return -(-self.latent_dim // 128) * 128

    @property
    def scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5


class MlaMoELayerWeights(NamedTuple):
    """One layer: MLA, four norms, and :mod:`.rope_moe`'s MLP leaves (a
    dense layer holds ``w1/w3/w2`` and None for the expert leaves, a MoE
    layer the reverse; ``e1/e3/e2`` hold the experts this chip holds)."""

    norm1: jnp.ndarray             # (H,) fp32, before attention
    w_dq: jnp.ndarray              # (H, q_rank)
    norm_q: jnp.ndarray            # (q_rank,)
    w_uq: jnp.ndarray              # (q_rank, heads * (nope + rope))
    w_dkv: jnp.ndarray             # (H, kv_rank + rope)
    norm_kv: jnp.ndarray           # (kv_rank,)
    w_uk: jnp.ndarray              # (heads, nope, kv_rank)
    w_uv: jnp.ndarray              # (heads, kv_rank, v)
    wo: jnp.ndarray                # (heads * v, H)
    norm1_post: jnp.ndarray        # (H,) after attention
    norm2: jnp.ndarray             # (H,) before the MLP
    norm2_post: jnp.ndarray        # (H,) after the MLP
    w1: Optional[jnp.ndarray]
    w3: Optional[jnp.ndarray]
    w2: Optional[jnp.ndarray]
    router: Optional[jnp.ndarray]  # (H, E) fp32, E every expert there is
    e1: Optional[jnp.ndarray]      # (held, H, Fe)
    e3: Optional[jnp.ndarray]
    e2: Optional[jnp.ndarray]      # (held, Fe, H)
    s1: Optional[jnp.ndarray]
    s3: Optional[jnp.ndarray]
    s2: Optional[jnp.ndarray]


class MtpWeights(NamedTuple):
    """The multi-token-prediction module: ``g_t = proj [N_h(h_t) ;
    N_e(Emb(x_{t+1}))]``, one MoE layer on ``g``, its final norm; the
    embedding and the head are the model's."""

    norm_h: jnp.ndarray            # (H,)
    norm_e: jnp.ndarray            # (H,)
    proj: jnp.ndarray              # (2H, H)
    layer: MlaMoELayerWeights
    norm_f: jnp.ndarray            # (H,)


def init_mla_moe_weights(key, cfg, *, dense_ffn: int, expert_ffn: int,
                         shared_ffn: int, experts_held: int,
                         mtp: bool = False,
                         std: float = 0.02) -> RopeMoEWeights:
    """Seeded random weights for ``cfg`` (an ``mla_moe``
    ``ServingModelConfig``), every leaf made on the device in ONE
    jitted call, as :func:`.rope_moe.init_rope_moe_weights` makes them;
    a MoE layer holds ``experts_held`` experts' stacks and the router of
    all ``cfg.num_experts``; ``mtp`` adds the MTP module."""
    if cfg.family != "mla_moe":
        raise ValueError(f"init_mla_moe_weights: family {cfg.family!r}")
    hidden, m, e = cfg.hidden_size, cfg.mla, cfg.num_experts

    def make(key):
        keys = map(functools.partial(jax.random.fold_in, key),
                   itertools.count(1))          # one a leaf, in order

        def mat(*shape, dtype=cfg.dtype):
            return (std * jax.random.normal(next(keys), shape,
                                            jnp.float32)).astype(dtype)

        def norm(width=hidden):
            return 1.0 + 0.1 * jax.random.normal(next(keys), (width,),
                                                 jnp.float32)

        # the model's leaves first, then the MTP module's (a MoE layer
        # of the model's own shape): a leaf's key is its place in this
        # order, so the model is the same with the module and without
        embed, norm_f = mat(cfg.vocab_size, hidden), norm()
        head = mat(hidden, cfg.vocab_size)
        specs = list(cfg.layers)
        if mtp:
            specs.append(dataclasses.replace(cfg.layers[-1], moe=True))
        layers = []
        for spec in specs:
            heads = spec.num_heads
            none = dict.fromkeys(MlaMoELayerWeights._fields)
            attn = dict(
                norm1=norm(), w_dq=mat(hidden, m.q_rank),
                norm_q=norm(m.q_rank),
                w_uq=mat(m.q_rank, heads * (m.nope_dim + m.rope_dim)),
                w_dkv=mat(hidden, m.latent_dim), norm_kv=norm(m.kv_rank),
                w_uk=mat(heads, m.nope_dim, m.kv_rank),
                w_uv=mat(heads, m.kv_rank, m.v_dim),
                wo=mat(heads * m.v_dim, hidden), norm1_post=norm(),
                norm2=norm(), norm2_post=norm())
            if spec.moe:
                mlp = dict(router=mat(hidden, e, dtype=jnp.float32),
                           e1=mat(experts_held, hidden, expert_ffn),
                           e3=mat(experts_held, hidden, expert_ffn),
                           e2=mat(experts_held, expert_ffn, hidden),
                           s1=mat(hidden, shared_ffn),
                           s3=mat(hidden, shared_ffn),
                           s2=mat(shared_ffn, hidden))
            else:
                mlp = dict(w1=mat(hidden, dense_ffn),
                           w3=mat(hidden, dense_ffn),
                           w2=mat(dense_ffn, hidden))
            layers.append(MlaMoELayerWeights(**{**none, **attn, **mlp}))
        n = len(cfg.layers)
        module = MtpWeights(norm_h=norm(), norm_e=norm(),
                            proj=mat(2 * hidden, hidden), layer=layers[n],
                            norm_f=norm()) if mtp else None
        return RopeMoEWeights(embed=embed, layers=tuple(layers[:n]),
                              norm_f=norm_f, head=head, mtp=module)

    return jax.jit(make)(key)


# --- attention ---------------------------------------------------------------

def _queries_and_latent(x, lw, cfg, spec, positions):
    """Residual stream (..., H) -> ``q_nope`` (..., heads, nope) and
    rotated ``q_rope`` (..., heads, rope), float32, and the token's
    latent row ``[N_kv(c_kv) ; RoPE(k_r) ; 0]`` (..., row_dim) in the
    model dtype, as the cache holds it."""
    m, eps = cfg.mla, cfg.layernorm_eps
    a_in = rms_norm(x, lw.norm1, eps)
    tables = rope_tables(positions, spec.rope)
    q = _mm(rms_norm(_mm(a_in, lw.w_dq), lw.norm_q, eps), lw.w_uq) \
        .reshape(*x.shape[:-1], spec.num_heads, m.nope_dim + m.rope_dim)
    q_rope = apply_rope(q[..., m.nope_dim:], positions, spec.rope, tables)
    kv = _mm(a_in, lw.w_dkv)
    k_rope = apply_rope(kv[..., None, m.kv_rank:], positions, spec.rope,
                        tables)[..., 0, :]
    latent = jnp.concatenate(
        [rms_norm(kv[..., :m.kv_rank], lw.norm_kv, eps), k_rope,
         _fill(k_rope, m)], -1)
    return q[..., :m.nope_dim], q_rope, latent.astype(cfg.dtype)


def _fill(like, m: MlaSpec):
    """The zeros that fill a row of ``latent_dim`` to ``row_dim``."""
    return jnp.zeros(like.shape[:-1] + (m.row_dim - m.latent_dim,),
                     like.dtype)


def _heads_out(ctx, lw):
    """Per-head values (..., heads, v) -> the attention branch (..., H)."""
    return _mm(ctx.reshape(*ctx.shape[:-2], -1), lw.wo)


def expanded(x, lw, cfg, spec, positions):
    """MLA with keys and values expanded from the latent: ``x`` (b, s,
    H) at ``positions`` (b, s) -> ``(branch (b, s, H) float32, latent
    rows (b, s, row_dim))``, causal over the sequence, through the flash
    forward kernel at QK width ``nope + rope`` and V width ``v`` (or its
    dense twin, ``cfg.prefill_flash`` off)."""
    from ..ops.flash_attention import flash_attention, mha_reference

    m, dt = cfg.mla, cfg.dtype
    with jax.named_scope("apex.attn.mla"):
        q_nope, q_rope, latent = _queries_and_latent(x, lw, cfg, spec,
                                                     positions)
        c_kv = latent[..., :m.kv_rank]
        k_nope = jnp.einsum("bsc,hnc->bhsn", c_kv, lw.w_uk,
                            preferred_element_type=jnp.float32)
        v = jnp.einsum("bsc,hcv->bhsv", c_kv, lw.w_uv,
                       preferred_element_type=jnp.float32).astype(dt)
        k_rope = jnp.broadcast_to(
            latent[:, None, :, m.kv_rank:m.latent_dim],
            k_nope.shape[:-1] + (m.rope_dim,))
        k = jnp.concatenate([k_nope.astype(dt), k_rope], -1)
        q = jnp.concatenate([q_nope, q_rope], -1).astype(dt) \
            .transpose(0, 2, 1, 3)
        attn = flash_attention if cfg.prefill_flash else mha_reference
        ctx = attn(q, k, v, scale=m.scale, causal=True)   # (b, h, s, v)
        return _heads_out(ctx.transpose(0, 2, 1, 3), lw), latent


def absorbed(x, lw, cfg, spec, cache_cfg, cache, layer, positions, write,
             block_tables, seq_lens):
    """MLA against the paged latent cache, ``W_uk`` and ``W_uv``
    absorbed: ``x`` (b, H) one token a row, or (b, t, H) a chunk a row,
    at ``positions`` -> ``(cache with the tokens' latents written to
    layer ``layer`` per ``write``, branch float32)``.  The tokens are
    written before they attend, so each sees itself through the cache."""
    m, dt = cfg.mla, cfg.dtype
    with jax.named_scope("apex.attn.mla"):
        q_nope, q_rope, latent = _queries_and_latent(x, lw, cfg, spec,
                                                     positions)
        cache = write_token_kv(cache, cache_cfg, layer,
                               latent[..., None, :], None, write)
        # (the two absorbed products leave in the model dtype, which
        # the kernel and the output projection would round them to)
        with jax.named_scope("apex.attn.mla.absorb"):
            q_abs = jnp.einsum("...hn,hnc->...hc", q_nope.astype(dt),
                               lw.w_uk)
        q_rope = q_rope.astype(dt)
        q_abs = jnp.concatenate([q_abs, q_rope, _fill(q_rope, m)], -1)
        one = x.ndim == 2                  # a token a row, or a chunk
        if cfg.decode_attention == "kernel":
            attn = latent_decode if one else latent_decode_multi
        else:
            attn = latent_attention_reference if one \
                else latent_attention_multi_reference
        u = attn(q_abs, cache.k[layer], block_tables, seq_lens,
                 value_dim=m.kv_rank, scale=m.scale)
        with jax.named_scope("apex.attn.mla.absorb"):
            ctx = jnp.einsum("...hc,hcv->...hv", u, lw.w_uv)
        return cache, _heads_out(ctx, lw)


# --- the MTP module ----------------------------------------------------------

def mtp_input(hidden, tokens_next, weights: RopeMoEWeights, eps):
    """``g_t = proj [N_h(h_t) ; N_e(Emb(x_{t+1}))]``: the target's final
    hidden states (..., H) and the token AFTER each position (...,) ->
    the MTP layer's input (..., H) float32."""
    w = weights.mtp
    e = jnp.take(weights.embed, tokens_next, axis=0).astype(jnp.float32)
    return _mm(jnp.concatenate([rms_norm(hidden, w.norm_h, eps),
                                rms_norm(e, w.norm_e, eps)], -1), w.proj)


def mtp_logits(g, weights: RopeMoEWeights, eps):
    """The MTP layer's output -> float32 logits for the token TWO past
    its position, through the module's norm and the model's head."""
    return _mm(rms_norm(g, weights.mtp.norm_f, eps), weights.head)
