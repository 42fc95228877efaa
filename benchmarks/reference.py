"""The plain references that decide ``correct``: the GPT-2 forward and
the BERT forward-and-loss in straightforward ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")`` -- no kernel, no cache, no
batching trick.  Written from the published equations (Radford et al.
2019 / Megatron-LM arXiv:1909.08053 for the pre-LayerNorm block, Devlin
et al. arXiv:1810.04805 for BERT's heads); they share no code with
``apex_tpu``.  They read the program's flax parameter tree by its leaf
names only.

Departures from the papers, all the program's and noted here: BERT is
in Megatron's pre-LayerNorm order with a final LayerNorm; gelu is the
tanh form in both models; the query/key/value projection is laid out
per head as [q | k | v] (Megatron's interleaving); the output
embedding is tied to the input embedding.
"""
from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

EPS = 1e-5


def plain_tree(params):
    """The parameter tree as float32 arrays, flax partitioning boxes
    removed."""
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                        nn.meta.unbox(params))


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * p["weight"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def linear(x, p):
    return x @ p["kernel"] + p["bias"]


def attention(x, p, heads, causal):
    """Multi-head self-attention over (b, s, hidden); every key is
    visible but, under ``causal``, those after the query."""
    b, s, hidden = x.shape
    d = hidden // heads
    qkv = linear(x, p["query_key_value"]).reshape(b, s, heads, 3 * d)
    q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        visible = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(visible, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, hidden)
    return linear(ctx, p["dense"])


def transformer(x, p, heads, causal):
    n_layers = sum(1 for k in p if k.startswith("layer_"))
    for i in range(n_layers):
        lp = p[f"layer_{i}"]
        x = x + attention(layer_norm(x, lp["input_layernorm"]),
                          lp["self_attention"], heads, causal)
        h = layer_norm(x, lp["post_attention_layernorm"])
        h = linear(gelu(linear(h, lp["mlp"]["dense_h_to_4h"])),
                   lp["mlp"]["dense_4h_to_h"])
        x = x + h
    return layer_norm(x, p["final_layernorm"])


def embed(tokens, p):
    s = tokens.shape[-1]
    return p["word_embeddings"]["embedding"][tokens] \
        + p["position_embeddings"]["embedding"][:s]


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def gpt_logits(params, tokens, heads):
    """(b, s) tokens -> (b, s, vocab) float32 logits."""
    with jax.default_matmul_precision("highest"):
        p = plain_tree(params)
        h = transformer(embed(tokens, p["embedding"]), p["transformer"],
                        heads, causal=True)
        return h @ p["embedding"]["word_embeddings"]["embedding"].T


def gpt_margins(params, tokens, emitted, heads):
    """For each position of (b, s) ``tokens``: how far the reference's
    logit of ``emitted`` (the token the system put next) lies under the
    reference's largest logit there -- 0 where the system chose the
    reference's arg-max -- and the spread (standard deviation) of the
    logits at that position, which says what a margin is small
    against."""
    logits = gpt_logits(params, tokens, heads)
    chosen = jnp.take_along_axis(logits, emitted[..., None], -1)[..., 0]
    return logits.max(-1) - chosen, logits.std(-1)


def gpt_loss_sum(params, tokens, labels, heads):
    """Sum over the batch's tokens of the next-token cross-entropy."""
    with jax.default_matmul_precision("highest"):
        return cross_entropy(gpt_logits(params, tokens, heads),
                             labels).sum()


def bert_loss_sums(params, tokens, labels, nsp, heads):
    """(sum of the LM cross-entropies, sum of the next-sentence
    cross-entropies) over the batch; all-ones padding mask, so every
    key is visible."""
    with jax.default_matmul_precision("highest"):
        p = plain_tree(params)
        h = transformer(embed(tokens, p["embedding"]), p["transformer"],
                        heads, causal=False)
        head = p["lm_head"]
        x = layer_norm(gelu(linear(h, head["dense"])), head["layernorm"])
        logits = x @ p["embedding"]["word_embeddings"]["embedding"].T \
            + head["bias"]
        pooled = jnp.tanh(linear(h[:, 0], p["pooler"]["dense"]))
        binary = linear(pooled, p["binary_head"])
        return (cross_entropy(logits, labels).sum(),
                cross_entropy(binary, nsp).sum())


def mean_loss_in_chunks(sum_fn, params, arrays, chunk):
    """``sum_fn(params, *arrays)`` jitted over ``chunk`` sequences at a
    time (so the reference's float32 logits never crowd the program's
    own peak memory); returns the per-chunk sums added up, as Python
    floats."""
    fn = jax.jit(sum_fn)
    totals = None
    for i in range(0, arrays[0].shape[0], chunk):
        out = fn(params, *(a[i:i + chunk] for a in arrays))
        vals = [float(o) for o in (out if isinstance(out, tuple)
                                   else (out,))]
        totals = vals if totals is None else \
            [t + v for t, v in zip(totals, vals)]
    return totals
