"""The plain reference of the ``evabyte`` configuration: the forward of
its decoder in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")`` -- no kernel, no cache, no
pages, no batching: a whole sequence at a time, attention as a masked
softmax over the keys a query may see.  Written from the equations of
ISSUE 36 (``PERF.md`` section 4 repeats them) and the published
``config.json``, whose keys it reads itself; it shares no code with
``apex_tpu`` and reads the program's weights by their leaf names only.

``h`` is the float32 residual stream (``fp32_skip_add``); ``N(x; w) = x /
sqrt(mean(x^2) + rms_norm_eps) * (1 + w)`` (``norm_add_unit_offset``);
``s = 1 / sqrt(d)``, ``d = hidden_size / num_attention_heads``; position
``t`` lies in chunk ``floor(t / chunk_size)`` and in window ``floor(t /
window_size)``.  No bias anywhere.  For each layer:

* ``a = N(h; norm1)``; per head ``q_t, k_t, v_t`` = rows of ``a wq, a
  wk, a wv``; ``q_t, k_t <- RoPE_t`` (``rope_theta``, all ``d`` dims, dim
  ``j`` paired with ``j + d/2``).
* **chunk pooling**, for a chunk ``c`` and the head's learned ``phi``,
  ``mu`` in R^d: ``a_j = softmax_{j in c}(s k_j . phi)``; ``k~_c = sum_j
  a_j k_j + mu``; ``v~_c = sum_j a_j v_j`` (pooled after the rotation).
* **attention** of query ``t``: ONE softmax of ``s q_t . key`` over the
  union of (a) ``(k_j, v_j)`` for ``j <= t`` in ``t``'s own window and
  (b) ``(k~_c, v~_c)`` for every chunk ``c`` of an EARLIER window (the
  current window's chunks are not seen pooled: its positions are seen
  exactly); ``o_t`` the weighted values; ``h <- h + concat_heads(o) wo``.
* ``m = N(h; norm2)``; ``h <- h + (silu(m w1) * (m w3)) w2``.

Head: ``logits_t = N(h_t; norm_f) head``, ``num_pred_heads x
vocab_size`` wide; columns ``[p V, (p + 1) V)`` are prediction head
``p``, for the byte at ``t + 1 + p``; head 0 is the next byte.

Departures, none of which changes a result: so that 32,768 positions of
the published widths fit beside the serving engine on one chip the
forward runs a window at a time (a window's queries see only their own
window and the pooled rows of the earlier ones, so a layer is a scan
over its windows that carries the pooled rows), queries a block at a
time inside a window, and the sequence is padded with zeros to whole
windows (padding lies in every real position's future); weights are
upcast from where they lie, a layer at a time (a bf16 number cast to
float32 is the same number); the pooled rows stay float32 here, where
the served cache rounds them to its storage type as it rounds every
key and value.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256         # queries scored against their keys at a time


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def rotate(x, positions, theta):
    """Rotary embedding of ``x`` (T, heads, d) at ``positions`` (T,):
    every dim, ``j`` paired with ``j + d/2``."""
    d = x.shape[-1]
    freqs = jnp.asarray([float(theta) ** (-2.0 * i / d)
                         for i in range(d // 2)], jnp.float32)
    angle = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def pool(k, v, phi, mu, chunk):
    """``k``, ``v`` (T, heads, d) of whole chunks -> the pooled key and
    value of each, (T / chunk, heads, d)."""
    t, heads, d = k.shape
    kc = k.reshape(t // chunk, chunk, heads, d)
    vc = v.reshape(t // chunk, chunk, heads, d)
    weight = jax.nn.softmax(
        jnp.einsum("cjhd,hd->cjh", kc, _f32(phi)) / math.sqrt(d), axis=1)
    return (jnp.einsum("cjh,cjhd->chd", weight, kc) + _f32(mu),
            jnp.einsum("cjh,cjhd->chd", weight, vc))


def window_attention(q, k, v, pooled_k, pooled_v, seen):
    """The queries ``q`` (W, heads, d) of ONE window over that window's
    ``k``, ``v`` causally and the first ``seen`` of the sequence's
    pooled rows ``pooled_k``, ``pooled_v`` (C, heads, d), one softmax;
    a block of queries at a time: (W, heads, d)."""
    w, heads, d = q.shape
    c = pooled_k.shape[0]
    block = min(QUERY_BLOCK, w)
    assert w % block == 0, "query blocks tile the window"
    own = jnp.arange(w)
    there = jnp.arange(c) < seen

    def one(args):
        qb, start = args
        at = start + jnp.arange(block)
        exact = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        exact = jnp.where(own[None, :] <= at[:, None], exact, -jnp.inf)
        summary = jnp.einsum("qhd,chd->hqc", qb, pooled_k) / math.sqrt(d)
        summary = jnp.where(there[None, None, :], summary, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([summary, exact], -1), -1)
        return jnp.einsum("hqc,chd->qhd", probs[..., :c], pooled_v) \
            + jnp.einsum("hqk,khd->qhd", probs[..., c:], v)

    out = jax.lax.map(one, (q.reshape(w // block, block, heads, d),
                            jnp.arange(w // block) * block))
    return out.reshape(w, heads, d)


def layer(x, lw, config: dict):
    """One block over ``x`` (n windows, W, hidden), a window at a time:
    the pooled rows of the windows done so far are carried along."""
    heads = config["num_attention_heads"]
    hidden = config["hidden_size"]
    d = hidden // heads
    window, chunk = config["window_size"], config["chunk_size"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    n = x.shape[0]
    per = window // chunk                   # pooled rows a window
    wq, wk, wv, wo = (_f32(w) for w in (lw.wq, lw.wk, lw.wv, lw.wo))
    w1, w3, w2 = (_f32(w) for w in (lw.w1, lw.w3, lw.w2))

    def one(carry, args):
        pooled_k, pooled_v = carry
        xw, i = args
        at = i * window + jnp.arange(window)
        a = norm(xw, lw.norm1, eps)
        q = rotate((a @ wq).reshape(window, heads, d), at, theta)
        k = rotate((a @ wk).reshape(window, heads, d), at, theta)
        v = (a @ wv).reshape(window, heads, d)
        o = window_attention(q, k, v, pooled_k, pooled_v, i * per)
        xw = xw + o.reshape(window, hidden) @ wo
        m = norm(xw, lw.norm2, eps)
        xw = xw + (jax.nn.silu(m @ w1) * (m @ w3)) @ w2
        pk, pv = pool(k, v, lw.phi, lw.mu, chunk)
        return (jax.lax.dynamic_update_slice_in_dim(pooled_k, pk, i * per, 0),
                jax.lax.dynamic_update_slice_in_dim(pooled_v, pv, i * per, 0)
                ), xw

    none = jnp.zeros((n * per, heads, d), jnp.float32)
    _, out = jax.lax.scan(one, (none, none), (x, jnp.arange(n)))
    return out


def hidden_states(weights, tokens, config: dict):
    """(T,) tokens -> the final residual stream (T, hidden)."""
    window = config["window_size"]
    t = tokens.shape[0]
    n = -(-t // window)
    padded = jnp.pad(tokens, (0, n * window - t))
    x = _f32(weights.embed)[padded].reshape(n, window, -1)
    for lw in weights.layers:
        x = layer(x, lw, config)
    return x.reshape(n * window, -1)[:t]


def logits(weights, tokens, config: dict):
    """(T,) tokens -> (T, num_pred_heads * vocab) float32 logits, every
    prediction head's: for the CPU tests' sizes."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(weights, tokens, config)
        return norm(x, weights.norm_f, config["rms_norm_eps"]) \
            @ _f32(weights.head)


def margins(weights, tokens, emitted, config: dict):
    """For each position of (b, s) ``tokens``: how far the reference's
    logit of ``emitted`` (the byte the system put next, sampled from
    prediction head 0) lies under head 0's largest logit there, and the
    spread (standard deviation) of head 0's logits at that position."""
    with jax.default_matmul_precision("highest"):
        vocab = config["vocab_size"]
        head = _f32(weights.head)[:, :vocab]

        def sequence(row, chosen):
            x = hidden_states(weights, row, config)
            lg = norm(x, weights.norm_f, config["rms_norm_eps"]) @ head
            took = jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0]
            return lg.max(-1) - took, lg.std(-1)

        outs = [sequence(tokens[i], emitted[i])
                for i in range(tokens.shape[0])]
        return tuple(jnp.stack(parts) for parts in zip(*outs))
