"""The plain reference of the ``laguna-xs2`` configuration: the forward
of its decoder in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")`` -- no kernel, no cache, no
batching trick, every expert computed for every token and the selected
ones weighted in, attention as a masked softmax over all keys.  Written
from the equations of ISSUE 29 (``PERF.md`` section 4 repeats them) and
the published ``config.json``, whose keys it reads itself; it shares no
code with ``apex_tpu`` and reads the program's weights by their leaf
names only.

For layer ``l`` (``x`` is ``(T, hidden)``, no bias anywhere, RMSNorm
``x / sqrt(mean(x^2) + eps) * w``):

* ``a = RMSNorm(x; norm1)``; ``q = a wq`` as ``(T, H_l, d)``, ``k = a
  wk``, ``v = a wv`` as ``(T, KV, d)``; query head ``j`` reads
  key/value head ``j // (H_l / KV)``.
* Rotary positions on the first ``partial_rotary_factor * d`` dims of
  ``q`` and ``k``, dim ``i`` paired with ``i + rot/2``; full layers
  with YaRN (the blend of ``inv_freq`` and ``inv_freq / factor`` by the
  linear ramp between the dims that make ``beta_fast`` and
  ``beta_slow`` turns in ``original_max_position_embeddings``
  positions, cos and sin times ``attention_factor``), sliding layers
  plain.
* ``P = softmax(q k^T / sqrt(d))`` over keys with ``pos_k <= pos_q``,
  on sliding layers also ``pos_q - pos_k < sliding_window``; ``c = P v``.
* ``c_h <- sigmoid(a wg)_h c_h``; ``x <- x + c wo``.
* ``m = RMSNorm(x; norm2)``.  Dense layers: ``x <- x + (silu(m w1) *
  (m w3)) w2``.  Sparse layers: ``s = sigmoid(m router)``; ``S`` the
  ``num_experts_per_tok`` largest; ``w_e = moe_routed_scaling_factor *
  s_e / sum_S s``; ``x <- x + sum_{e in S} w_e FFN_e(m) +
  FFN_shared(m)``, every FFN that SwiGLU.  No capacity: nothing is
  dropped.
* ``logits = RMSNorm(x; norm_f) head``.

So that 8,704 positions of the published widths fit beside the serving
engine on one chip, the forward runs in blocks -- queries a block at a
time, experts a group at a time, upcast from the bf16 weights where they
lie (a bf16 number cast to float32 is the same number), the head a block
of positions at a time -- which changes no result.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256         # queries scored against all keys at a time
EXPERT_GROUP = 8          # experts upcast and applied at a time
HEAD_BLOCK = 512          # positions projected onto the vocabulary


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def inv_frequencies(params: dict, head_dim: int):
    """(rotary dims / 2,) angular frequencies of one layer kind and the
    factor on cos and sin, from its entry of ``rope_parameters``."""
    rot = int(params["partial_rotary_factor"] * head_dim)
    base = float(params["rope_theta"])
    plain = [base ** (-2.0 * i / rot) for i in range(rot // 2)]
    if params["rope_type"] != "yarn":
        return jnp.asarray(plain, jnp.float32), 1.0, rot
    factor = float(params["factor"])
    original = params["original_max_position_embeddings"]

    def dim_of(turns):
        # the (fractional) pair index whose wavelength makes ``turns``
        # turns in ``original`` positions
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(params["beta_fast"])), 0)
    high = min(math.ceil(dim_of(params["beta_slow"])), rot - 1)
    blended = []
    for i, f in enumerate(plain):
        share = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        blended.append(f / factor * share + f * (1.0 - share))
    return (jnp.asarray(blended, jnp.float32),
            float(params["attention_factor"]), rot)


def rotate(x, positions, params: dict):
    """Rotary embedding of ``x`` (T, heads, d) at ``positions`` (T,)."""
    freqs, factor, rot = inv_frequencies(params, x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    lo, hi, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin, rest], -1)


def _blocks(n: int, block: int):
    block = min(block, n)
    return block, -(-n // block)


def attention(q, k, v, window):
    """Causal (and, with ``window``, banded) softmax attention of
    ``q`` (T, H, d) over ``k``, ``v`` (T, KV, d), a block of queries at
    a time: (T, H, d)."""
    t, heads, d = q.shape
    kv = k.shape[1]
    block, n = _blocks(t, QUERY_BLOCK)
    pad = n * block - t
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(n, block, kv, heads // kv, d)
    key_pos = jnp.arange(t)

    def one(args):
        qb, start = args
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(d)
        q_pos = start + jnp.arange(block)
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen &= q_pos[:, None] - key_pos[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v)

    out = jax.lax.map(one, (qs, jnp.arange(n) * block))
    return out.reshape(n * block, heads, d)[:t]


def swiglu(m, w1, w3, w2):
    return (jax.nn.silu(m @ _f32(w1)) * (m @ _f32(w3))) @ _f32(w2)


def routing(m, router, k: int, scaling: float):
    """(dense combine weights (T, E), the score gap between the k-th
    and the (k+1)-th expert (T,)): zero for the unselected experts."""
    scores = jax.nn.sigmoid(m @ _f32(router))
    top, ids = jax.lax.top_k(scores, k + 1)
    weights = scaling * top[:, :k] / top[:, :k].sum(-1, keepdims=True)
    dense = jnp.zeros_like(scores).at[
        jnp.arange(m.shape[0])[:, None], ids[:, :k]].set(weights)
    return dense, top[:, k - 1] - top[:, k]


def experts(m, lw, combine):
    """``sum_e combine[:, e] FFN_e(m)``: EVERY expert applied to every
    token, a group of experts at a time."""
    e = lw.e1.shape[0]
    group, n = _blocks(e, EXPERT_GROUP)
    assert n * group == e, "expert groups must tile the experts"

    def add(i, total):
        def cut(w):
            return _f32(jax.lax.dynamic_slice_in_dim(w, i * group, group))

        hidden = jax.nn.silu(jnp.einsum("th,ehf->etf", m, cut(lw.e1))) \
            * jnp.einsum("th,ehf->etf", m, cut(lw.e3))
        out = jnp.einsum("etf,efh->eth", hidden, cut(lw.e2))
        share = jax.lax.dynamic_slice_in_dim(combine, i * group, group, 1)
        return total + jnp.einsum("eth,te->th", out, share)

    return jax.lax.fori_loop(0, n, add, jnp.zeros_like(m))


def hidden_states(weights, tokens, config: dict):
    """(T,) tokens -> (final residual stream (T, hidden), the smallest
    k-th/(k+1)-th router score gap over the sparse layers (T,))."""
    d, kv = config["head_dim"], config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    t = tokens.shape[0]
    positions = jnp.arange(t)
    x = _f32(weights.embed[tokens])
    gap = jnp.full((t,), jnp.inf)
    for i, lw in enumerate(weights.layers):
        kind = config["layer_types"][i]
        heads = config["num_attention_heads_per_layer"][i]
        rope = config["rope_parameters"][kind]
        a = rms_norm(x, lw.norm1, eps)
        q = rotate((a @ _f32(lw.wq)).reshape(t, heads, d), positions, rope)
        k = rotate((a @ _f32(lw.wk)).reshape(t, kv, d), positions, rope)
        v = (a @ _f32(lw.wv)).reshape(t, kv, d)
        c = attention(q, k, v, config["sliding_window"]
                      if kind == "sliding_attention" else None)
        c = c * jax.nn.sigmoid(a @ _f32(lw.wg))[:, :, None]
        x = x + c.reshape(t, heads * d) @ _f32(lw.wo)
        m = rms_norm(x, lw.norm2, eps)
        if config["mlp_layer_types"][i] == "dense":
            x = x + swiglu(m, lw.w1, lw.w3, lw.w2)
        else:
            combine, layer_gap = routing(
                m, lw.router, config["num_experts_per_tok"],
                config["moe_routed_scaling_factor"])
            gap = jnp.minimum(gap, layer_gap)
            x = x + experts(m, lw, combine) \
                + swiglu(m, lw.s1, lw.s3, lw.s2)
    return x, gap


def logits(weights, tokens, config: dict):
    """(T,) tokens -> (T, vocab) float32 logits, all at once: for the
    CPU tests' sizes."""
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_states(weights, tokens, config)
        return rms_norm(x, weights.norm_f, config["rms_norm_eps"]) \
            @ _f32(weights.head)


def margins(weights, tokens, emitted, config: dict):
    """For each position of (b, s) ``tokens``: how far the reference's
    logit of ``emitted`` (the token the system put next) lies under the
    reference's largest logit there, the spread (standard deviation) of
    the logits at that position, and the smallest router score gap
    between a selected and the best unselected expert (what a margin
    above rounding may be owed to: ``PERF.md`` section 6, PR 29)."""
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        head = _f32(weights.head)

        def sequence(row, chosen):
            x, gap = hidden_states(weights, row, config)
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
            return margin.reshape(-1)[:t], spread.reshape(-1)[:t], gap

        outs = [sequence(tokens[i], emitted[i])
                for i in range(tokens.shape[0])]
        return tuple(jnp.stack(parts) for parts in zip(*outs))
