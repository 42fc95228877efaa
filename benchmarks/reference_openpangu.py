"""The plain reference of the ``openpangu-ultra-moe`` configuration: the
forward of its decoder in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")`` -- no kernel, no cache, no
absorbed form: latent attention is **expanded** (keys and values made
from the latent for every token and head), attention is a masked softmax
over all keys, and each held expert is applied to every token and the
selected ones weighted in.  Written from the equations of ISSUE 34
(``PERF.md`` section 4 repeats them) and the published ``config.json``,
whose keys it reads itself; it shares no code with ``apex_tpu`` and reads
the program's weights by their leaf names only.

``x`` is the float32 residual stream ``(T, hidden)``; ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``; no bias anywhere.  For layer ``l``:

* ``a = N(x; norm1)``; ``c_q = N(a w_dq; norm_q)``; ``[q_nope ;
  q_rope]_i = c_q w_uq`` for each of the heads, ``qk_nope_head_dim +
  qk_rope_head_dim`` wide.
* ``[c_kv ; k_r] = a w_dkv``; ``c_kv <- N(c_kv; norm_kv)``; ``k_rope =
  RoPE(k_r)``, one for all heads; ``q_rope_i <- RoPE(q_rope_i)``; theta
  ``rope_theta`` on all ``qk_rope_head_dim`` dims, dim ``i`` paired with
  ``i + rot/2``, no scaling.
* ``k_nope_i = c_kv w_uk_i^T``, ``v_i = c_kv w_uv_i`` (the two halves of
  the published ``kv_b_proj``).
* ``P_i = softmax((q_nope_i . k_nope_i + q_rope_i . k_rope) /
  sqrt(nope + rope))`` over keys with ``pos_k <= pos_q``; ``o = concat_i
  (P_i v_i) wo``.
* Sandwich norms: ``x <- x + N(o; norm1_post)``; ``m = N(x; norm2)``;
  ``x <- x + N(MLP(m); norm2_post)``.
* ``MLP``: on the first ``first_k_dense_replace`` layers ``(silu(m w1) *
  (m w3)) w2``.  After them ``s = sigmoid(m router)`` over ALL the
  experts the router scores; ``S`` the ``num_experts_per_tok`` largest;
  ``w_e = routed_scaling_factor * s_e / sum_S s``; ``MLP(m) =
  FFN_shared(m) + sum_{e in S and held here} w_e FFN_e(m)``, every FFN
  that SwiGLU.  The weights hold the experts ``expert_first ..
  expert_first + e1.shape[0]`` (``deployment_share``): the sum over the
  experts of the other chips of the deployment is theirs and is not
  made here, as it is not in the system.  No capacity: nothing dropped.
* ``logits = N(x; norm_f) head`` over the vocabulary rows held here.
* The MTP module, where the weights bring one (``weights.mtp``): ``g_t
  = proj [N(x_t; norm_h) ; N(embed[token_{t+1}]; norm_e)]``, one layer
  of the form above (a MoE layer) on ``g`` with causal attention over
  its own positions, ``mtp_logits_t = N(g_t; mtp.norm_f) head``: for
  ``token_{t+2}``.

Assumptions (the configuration file's ``assumed`` gives each its why):
the router's form, rotate-half pairing, where the sandwich norms sit,
hidden state first in the MTP's concatenation.

So that 4,608 positions of the published widths fit beside the serving
engine on one chip, the forward runs in blocks -- queries a block at a
time, the dense MLP a slice of its width at a time, experts a group at a
time, the head a block of positions at a time, upcast from the bf16
weights where they lie (a bf16 number cast to float32 is the same
number) -- which changes no result.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 128         # queries scored against all keys at a time
FFN_SLICE = 2048          # columns of a dense MLP upcast at a time
EXPERT_GROUP = 2          # experts upcast and applied at a time
HEAD_BLOCK = 512          # positions projected onto the vocabulary


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def rotate(x, positions, theta: float):
    """Rotary embedding of ``x`` (T, heads, rot) at ``positions`` (T,):
    every dim rotates, dim ``i`` with dim ``i + rot/2``."""
    rot = x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / rot)
                         for i in range(rot // 2)], jnp.float32)
    angle = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def _blocks(n: int, block: int):
    block = min(block, n)
    return block, -(-n // block)


def attention(q, k, v):
    """Causal softmax attention of ``q`` (T, H, dqk) over ``k`` (T, H,
    dqk), ``v`` (T, H, dv), a block of queries at a time: (T, H, dv)."""
    t, heads, d = q.shape
    block, n = _blocks(t, QUERY_BLOCK)
    qs = jnp.pad(q, ((0, n * block - t), (0, 0), (0, 0))) \
        .reshape(n, block, heads, d)
    key_pos = jnp.arange(t)

    def one(args):
        qb, start = args
        scores = jnp.einsum("qhd,thd->hqt", qb, k) / math.sqrt(d)
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqt,thd->qhd", probs, v)

    out = jax.lax.map(one, (qs, jnp.arange(n) * block))
    return out.reshape(n * block, heads, v.shape[-1])[:t]


def latent_attention(x, lw, positions, config: dict):
    """The attention branch of one layer, expanded: (T, hidden)."""
    eps = config["rms_norm_eps"]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, theta = config["kv_lora_rank"], float(config["rope_theta"])
    t = x.shape[0]
    a = rms_norm(x, lw.norm1, eps)
    c_q = rms_norm(a @ _f32(lw.w_dq), lw.norm_q, eps)
    q = (c_q @ _f32(lw.w_uq)).reshape(t, heads, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], positions, theta)], -1)
    kv = a @ _f32(lw.w_dkv)
    c_kv = rms_norm(kv[:, :rank], lw.norm_kv, eps)
    k_rope = rotate(kv[:, None, rank:], positions, theta)
    k_nope = jnp.einsum("tc,hnc->thn", c_kv, _f32(lw.w_uk))
    v = jnp.einsum("tc,hcv->thv", c_kv, _f32(lw.w_uv))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (t, heads, rope))], -1)
    return attention(q, k, v).reshape(t, -1) @ _f32(lw.wo)


def swiglu(m, w1, w3, w2):
    return (jax.nn.silu(m @ _f32(w1)) * (m @ _f32(w3))) @ _f32(w2)


def dense_mlp(m, lw):
    """``swiglu`` a slice of its width at a time."""
    width = lw.w1.shape[1]
    cut, n = _blocks(width, FFN_SLICE)
    assert n * cut == width, "the slices must tile the MLP's width"

    def add(i, total):
        def cols(w):
            return _f32(jax.lax.dynamic_slice_in_dim(w, i * cut, cut, 1))

        hidden = jax.nn.silu(m @ cols(lw.w1)) * (m @ cols(lw.w3))
        return total + hidden @ _f32(
            jax.lax.dynamic_slice_in_dim(lw.w2, i * cut, cut, 0))

    return jax.lax.fori_loop(0, n, add, jnp.zeros_like(m))


def routing(m, router, k: int, scaling: float):
    """Dense combine weights (T, E) over every expert the router scores:
    zero for the unselected."""
    scores = jax.nn.sigmoid(m @ _f32(router))
    top, ids = jax.lax.top_k(scores, k)
    weights = scaling * top / top.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(m.shape[0])[:, None], ids].set(weights)


def held_experts(m, lw, combine, first: int):
    """``sum_e combine[:, e] FFN_e(m)`` over the experts the weights
    hold (``first`` on): each applied to every token, a group at a
    time."""
    held = lw.e1.shape[0]
    group, n = _blocks(held, EXPERT_GROUP)
    assert n * group == held, "expert groups must tile the held experts"
    share = jax.lax.dynamic_slice_in_dim(combine, first, held, 1)

    def add(i, total):
        def cut(w):
            return _f32(jax.lax.dynamic_slice_in_dim(w, i * group, group))

        hidden = jax.nn.silu(jnp.einsum("th,ehf->etf", m, cut(lw.e1))) \
            * jnp.einsum("th,ehf->etf", m, cut(lw.e3))
        out = jnp.einsum("etf,efh->eth", hidden, cut(lw.e2))
        weights = jax.lax.dynamic_slice_in_dim(share, i * group, group, 1)
        return total + jnp.einsum("eth,te->th", out, weights)

    return jax.lax.fori_loop(0, n, add, jnp.zeros_like(m))


def layer(x, lw, positions, config: dict):
    """One decoder layer with its sandwich norms."""
    eps = config["rms_norm_eps"]
    x = x + rms_norm(latent_attention(x, lw, positions, config),
                     lw.norm1_post, eps)
    m = rms_norm(x, lw.norm2, eps)
    if lw.router is None:
        branch = dense_mlp(m, lw)
    else:
        combine = routing(m, lw.router, config["num_experts_per_tok"],
                          config["routed_scaling_factor"])
        branch = swiglu(m, lw.s1, lw.s3, lw.s2) + held_experts(
            m, lw, combine, config["deployment_share"]["expert_first"])
    return x + rms_norm(branch, lw.norm2_post, eps)


def hidden_states(weights, tokens, config: dict):
    """(T,) tokens -> the final residual stream (T, hidden)."""
    positions = jnp.arange(tokens.shape[0])
    x = _f32(weights.embed[tokens])
    for i, lw in enumerate(weights.layers):
        assert (lw.router is None) == (i < config["first_k_dense_replace"])
        x = layer(x, lw, positions, config)
    return x


def mtp_hidden(weights, x, tokens_next, config: dict):
    """The MTP module on the final residual stream ``x`` (T, hidden) and
    the token after each position (T,): its layer's output (T, hidden)."""
    eps, w = config["rms_norm_eps"], weights.mtp
    g = jnp.concatenate(
        [rms_norm(x, w.norm_h, eps),
         rms_norm(_f32(weights.embed[tokens_next]), w.norm_e, eps)], -1) \
        @ _f32(w.proj)
    return layer(g, w.layer, jnp.arange(x.shape[0]), config)


def logits(weights, tokens, config: dict, tokens_next=None):
    """(T,) tokens -> (T, vocab) float32 logits, all at once (for the
    CPU tests' sizes); with ``tokens_next`` (T,), the token after each
    position, also the MTP module's logits (T, vocab) for the token two
    past each position, as a second output."""
    with jax.default_matmul_precision("highest"):
        eps, head = config["rms_norm_eps"], _f32(weights.head)
        x = hidden_states(weights, tokens, config)
        main = rms_norm(x, weights.norm_f, eps) @ head
        if tokens_next is None:
            return main
        g = mtp_hidden(weights, x, tokens_next, config)
        return main, rms_norm(g, weights.mtp.norm_f, eps) @ head


def margins(weights, tokens, emitted, config: dict):
    """For each position of (b, s) ``tokens``: how far the reference's
    logit of ``emitted`` (the token the system put next) lies under the
    reference's largest logit there, and the spread (standard deviation)
    of the logits at that position."""
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        head = _f32(weights.head)

        def sequence(row, chosen):
            x = hidden_states(weights, row, config)
            t = x.shape[0]
            block, n = _blocks(t, HEAD_BLOCK)
            pad = n * block - t
            xs = jnp.pad(rms_norm(x, weights.norm_f, eps),
                         ((0, pad), (0, 0))).reshape(n, block, -1)
            cs = jnp.pad(chosen, (0, pad)).reshape(n, block)

            def one(args):
                xb, cb = args
                lg = xb @ head
                took = jnp.take_along_axis(lg, cb[:, None], -1)[:, 0]
                return lg.max(-1) - took, lg.std(-1)

            margin, spread = jax.lax.map(one, (xs, cs))
            return margin.reshape(-1)[:t], spread.reshape(-1)[:t]

        outs = [sequence(tokens[i], emitted[i])
                for i in range(tokens.shape[0])]
        return tuple(jnp.stack(parts) for parts in zip(*outs))
