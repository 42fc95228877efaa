"""The engine holds its weights in the dtype its programs read
(``serving.model.weights_in_compute_dtype``): the rule leaf by leaf, the
identity where a tree is already in that dtype, and that a float32 GPT
tree handed to a bf16 engine serves the tokens and logits it served when
every program cast it -- bit for bit, since a float32 value rounds to
the same bf16 once or on every tick.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving
from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                              ServingModelConfig, default_cache_config,
                              expand_moe_weights, extract_serving_weights,
                              init_cache, quantize_weights,
                              weights_in_compute_dtype)
from apex_tpu.serving.model import decode_logits
from apex_tpu.testing.standalone_gpt import GPTModel

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8
VOCAB, HIDDEN, HEADS, LAYERS, SEQ = 64, 32, 2, 2, 32
BLOCKS, BLOCK = 16, 4
LADDER = BucketLadder(batch=(2,), pages=(3,))
PROMPTS = [[3, 7, 1], [11, 2, 9, 4, 5], [1, 2], [6, 6, 6, 6]]
KERNELS = ("qkv_k", "dense_k", "fc1_k", "fc2_k")
BIASES = ("qkv_b", "dense_b", "fc1_b", "fc2_b")
NORMS = ("ln1_w", "ln1_b", "ln2_w", "ln2_b")


def gpt_weights(seed=0):
    """A float32 GPT tree with no leaf at its initial value (biases 0,
    LayerNorm 1 / 0 round to themselves in any dtype and would hide a
    leaf cast that should not be)."""
    model = GPTModel(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_attention_heads=HEADS, max_sequence_length=SEQ,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=BF16)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.013 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])
    cfg = ServingModelConfig.from_model(
        model, prefill_flash=False, decode_attention="reference")
    assert cfg.dtype == BF16
    weights = extract_serving_weights(params, LAYERS)
    assert {leaf.dtype for leaf in jax.tree.leaves(weights)} == {
        jnp.dtype(F32)}
    return cfg, weights


def as_given(weights, tier):
    return quantize_weights(weights) if tier == "Q8" else weights


def engine(weights, cfg, **kw):
    ccfg = default_cache_config(cfg, num_blocks=BLOCKS, block_size=BLOCK)
    return ServingEngine(weights, cfg, ccfg, ladder=LADDER, **kw)


def served_tokens(eng, new_tokens=6):
    for i, p in enumerate(PROMPTS):
        eng.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=new_tokens))
    eng.run()
    assert len(eng.done) == len(PROMPTS)
    return {q.rid: q.out_tokens for q in eng.done}


def one_tick_logits(weights, cfg):
    """``decode_logits`` of two rows on a fresh cache: (2, V)."""
    ccfg = default_cache_config(cfg, num_blocks=BLOCKS, block_size=BLOCK)
    ints = lambda *v: jnp.asarray(v, jnp.int32)
    _, logits, _ = decode_logits(
        weights, cfg, ccfg, init_cache(ccfg), ints(5, 9), ints(0, 0),
        jnp.asarray([[1, 0, 0], [2, 0, 0]], jnp.int32), ints(1, 1),
        ints(1, 2), ints(0, 0))
    return np.asarray(logits.astype(F32))


class Events:
    """A monitor that keeps the ``serving`` events it is sent."""

    def __init__(self):
        self.events = []

    def event(self, kind, name, value=None, step=None, **attrs):
        self.events.append((name, attrs))

    def named(self, name):
        return [attrs for n, attrs in self.events if n == name]


def nbytes(tree):
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree))


# --- the rule ---------------------------------------------------------------

def all_are(node, names, dtype):
    return all(getattr(node, n).dtype == dtype for n in names)


def test_dense_gpt_leaves():
    cfg, weights = gpt_weights()
    held = weights_in_compute_dtype(weights, cfg)
    assert held.wte.dtype == held.wpe.dtype == BF16
    assert held.lnf_w.dtype == held.lnf_b.dtype == F32
    for lw in held.layers:
        assert all_are(lw, KERNELS + BIASES, BF16), lw
        assert all_are(lw, NORMS, F32), lw
    # the caller's tree is not touched, and the values are its own
    assert weights.wte.dtype == F32
    np.testing.assert_array_equal(
        np.asarray(held.layers[1].fc1_k.astype(F32)),
        np.asarray(weights.layers[1].fc1_k.astype(BF16).astype(F32)))
    assert held.layers[0].ln1_w is weights.layers[0].ln1_w


def test_q8_leaves():
    cfg, weights = gpt_weights()
    given = quantize_weights(weights)
    held = weights_in_compute_dtype(given, cfg)
    assert type(held) is type(given)
    assert held.wte.dtype == held.wpe.dtype == BF16
    for lw, gw in zip(held.layers, given.layers):
        assert all_are(lw, KERNELS, I8), lw
        assert all_are(lw, ("qkv_s", "dense_s", "fc1_s", "fc2_s"), F32)
        assert all_are(lw, BIASES, BF16) and all_are(lw, NORMS, F32)
        assert lw.qkv_k is gw.qkv_k and lw.fc2_s is gw.fc2_s


def test_switch_moe_leaves():
    cfg, weights = gpt_weights()
    given = expand_moe_weights(weights, 4, jax.random.PRNGKey(0))
    held = weights_in_compute_dtype(given, cfg)
    for lw, gw in zip(held.layers, given.layers):
        assert all_are(lw, ("qkv_k", "qkv_b", "dense_k", "dense_b", "wi",
                            "wo"), BF16), lw
        assert all_are(lw, NORMS + ("router",), F32), lw
        assert lw.router is gw.router


@pytest.mark.parametrize("tier", ["O5", "Q8"])
def test_identity_under_a_float32_policy(tier):
    cfg, weights = gpt_weights()
    given = as_given(weights, tier)
    held = weights_in_compute_dtype(given,
                                    dataclasses.replace(cfg, dtype=F32))
    assert held is given
    for g, h in zip(jax.tree.leaves(given), jax.tree.leaves(held)):
        assert h is g


@pytest.mark.parametrize("tier", ["O5", "Q8"])
def test_a_held_tree_is_held_as_it_is(tier):
    cfg, weights = gpt_weights()
    once = weights_in_compute_dtype(as_given(weights, tier), cfg)
    twice = weights_in_compute_dtype(once, cfg)
    assert twice is once
    for a, b in zip(jax.tree.leaves(once), jax.tree.leaves(twice)):
        assert b is a


def test_shapes_alone_are_enough():
    """What the compile tests do: the rule on ``ShapeDtypeStruct``s."""
    cfg, weights = gpt_weights()
    shapes = jax.eval_shape(lambda w: weights_in_compute_dtype(w, cfg),
                            weights)
    held = weights_in_compute_dtype(weights, cfg)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), shapes) \
        == jax.tree.map(lambda x: (x.shape, x.dtype), held)


def rope_moe_tree():
    layers = tuple(
        serving.LayerSpec(num_heads=4, window=w, moe=moe,
                          rope=serving.RopeSpec(theta=1e4, rotary_dim=16))
        for w, moe in ((None, False), (8, True)))
    cfg = ServingModelConfig(
        vocab_size=96, hidden_size=32, num_heads=4, num_layers=2,
        max_seq=32, dtype=BF16, layernorm_eps=1e-6, num_experts=4,
        head_dim=16, num_kv_heads=2, family="rope_moe", layers=layers,
        experts_per_token=2, routed_scaling=2.5,
        decode_attention="reference", prefill_flash=False)
    return cfg, serving.init_rope_moe_weights(
        jax.random.PRNGKey(0), cfg, dense_ffn=64, expert_ffn=16,
        shared_ffn=16)


def mla_moe_tree(mtp):
    rope = serving.RopeSpec(theta=25.6e6, rotary_dim=8)
    cfg = ServingModelConfig(
        vocab_size=96, hidden_size=32, num_heads=4, num_layers=2,
        max_seq=32, dtype=BF16, num_experts=8, family="mla_moe",
        layers=tuple(serving.LayerSpec(num_heads=4, window=None,
                                       rope=rope, moe=moe)
                     for moe in (False, True)),
        experts_per_token=2, routed_scaling=2.5,
        mla=serving.MlaSpec(q_rank=24, kv_rank=32, nope_dim=16,
                            rope_dim=8, v_dim=12),
        mtp_layers=int(mtp), decode_attention="reference",
        prefill_flash=False)
    return cfg, serving.init_mla_moe_weights(
        jax.random.PRNGKey(0), cfg, dense_ffn=64, expert_ffn=16,
        shared_ffn=16, experts_held=4, mtp=mtp)


ROUTED_TREES = {
    "rope_moe": rope_moe_tree,
    "mla_moe": lambda: mla_moe_tree(False),
    "mla_moe_mtp": lambda: mla_moe_tree(True),
}


@pytest.mark.parametrize("name", list(ROUTED_TREES))
def test_routed_families_come_back_as_given(name):
    """7.7 and 9.9 GB of weights on a 16 GB chip: nothing is copied."""
    cfg, weights = ROUTED_TREES[name]()
    if name == "mla_moe_mtp":
        assert weights.mtp is not None
    assert {leaf.dtype for leaf in jax.tree.leaves(weights)} == {
        jnp.dtype(BF16), jnp.dtype(F32)}
    held = weights_in_compute_dtype(weights, cfg)
    assert held is weights
    given = jax.tree.leaves(weights)
    assert len(given) > 20
    for g, h in zip(given, jax.tree.leaves(held), strict=True):
        assert h is g


@pytest.mark.parametrize("name", list(ROUTED_TREES))
def test_engine_holds_a_routed_tree_itself(name):
    cfg, weights = ROUTED_TREES[name]()
    monitor = Events()
    ccfg = default_cache_config(cfg, num_blocks=BLOCKS, block_size=BLOCK,
                                kv_dtype="model")
    eng = ServingEngine(weights, cfg, ccfg, ladder=LADDER, monitor=monitor,
                        speculate_k=cfg.mtp_layers, prefill_chunk=0,
                        prefix_share=False, slo=None)
    assert eng.weights is weights
    (held,) = monitor.named("weights_held")
    assert held == dict(tree="target", leaves=len(jax.tree.leaves(weights)),
                        leaves_cast=0, bytes_given=nbytes(weights),
                        bytes_held=nbytes(weights))


# --- the engine -------------------------------------------------------------

@pytest.mark.parametrize("tier", ["O5", "Q8"])
def test_float32_given_serves_what_pre_cast_serves(tier):
    cfg, weights = gpt_weights()
    given = as_given(weights, tier)
    cast = weights_in_compute_dtype(given, cfg)
    eng_given, eng_cast = engine(given, cfg), engine(cast, cfg)
    # the engine that was handed the cast tree holds those very arrays
    for a, b in zip(jax.tree.leaves(cast), jax.tree.leaves(eng_cast.weights)):
        assert b is a
    tokens = served_tokens(eng_given)
    assert tokens == served_tokens(eng_cast)
    assert len({tuple(t) for t in tokens.values()}) > 1
    # and the step's own casts of the float32 tree (every tick's work
    # before the engine held its weights) made the same logits
    in_step = one_tick_logits(given, cfg)
    assert np.ptp(in_step) > 0
    np.testing.assert_array_equal(in_step,
                                  one_tick_logits(eng_given.weights, cfg))
    np.testing.assert_array_equal(in_step,
                                  one_tick_logits(eng_cast.weights, cfg))


@pytest.mark.parametrize("tier", ["O5", "Q8"])
def test_event_and_snapshot_say_what_is_held(tier):
    cfg, weights = gpt_weights()
    given = as_given(weights, tier)
    monitor = Events()
    eng = engine(given, cfg, monitor=monitor)
    (held,) = monitor.named("weights_held")
    per_layer = 8 if tier == "O5" else 4      # kernels + biases, or biases
    assert held["tree"] == "target"
    assert held["leaves"] == len(jax.tree.leaves(given))
    assert held["leaves_cast"] == 2 + LAYERS * per_layer
    assert held["bytes_given"] == nbytes(given)
    assert held["bytes_held"] == nbytes(eng.weights) < nbytes(given)
    assert eng.router_snapshot()["weight_bytes"] == held["bytes_held"]
    if tier == "O5":
        # all but the LayerNorm vectors halves
        norms = 4 * (2 + LAYERS * 4) * HIDDEN
        assert held["bytes_held"] == (held["bytes_given"] - norms) // 2 \
            + norms


def test_float32_policy_engine_holds_the_given_arrays():
    cfg, weights = gpt_weights()
    monitor = Events()
    eng = engine(weights, dataclasses.replace(cfg, dtype=F32),
                 monitor=monitor)
    assert eng.weights is weights
    (held,) = monitor.named("weights_held")
    assert held["leaves_cast"] == 0
    assert held["bytes_held"] == held["bytes_given"] == nbytes(weights)


def test_swap_takes_a_float32_tree_without_a_compile():
    cfg, weights = gpt_weights(seed=0)
    _, fresh = gpt_weights(seed=1)
    monitor = Events()
    eng = engine(weights, cfg, monitor=monitor)
    before = served_tokens(eng)
    eng.done.clear()
    compiles = dict(eng._compiles)
    assert sum(compiles.values()) > 0
    eng.swap_weights(fresh)                     # float32 into bf16
    assert eng.weights.wte.dtype == BF16
    assert eng.weights.layers[0].ln1_w is fresh.layers[0].ln1_w
    after = served_tokens(eng)
    assert dict(eng._compiles) == compiles
    assert after == served_tokens(engine(fresh, cfg))
    assert after != before
    assert [e["leaves_cast"] for e in monitor.named("weights_held")] \
        == [2 + LAYERS * 8] * 2
    (swapped,) = monitor.named("weights_swapped")
    assert swapped["requantized"] is False


def test_requantization_swap_from_float32_keeps_its_rules():
    cfg, weights = gpt_weights()
    eng = engine(weights, cfg)
    served_tokens(eng)
    eng.done.clear()
    eng.swap_weights(quantize_weights(weights))
    assert eng.weights.layers[0].qkv_k.dtype == I8
    assert eng.weights.layers[0].qkv_b.dtype == BF16
    assert eng.weights.wte.dtype == BF16
    want = served_tokens(engine(quantize_weights(weights), cfg))
    assert served_tokens(eng) == want
    # a tree of another geometry is still refused, whatever its dtype
    with pytest.raises(ValueError, match="swap_weights leaf"):
        eng.swap_weights(quantize_weights(weights)._replace(
            wte=jnp.zeros((VOCAB, 2 * HIDDEN), F32)))
    assert eng.router_snapshot()["weight_bytes"] == nbytes(eng.weights)


def test_draft_tree_is_held_too():
    cfg, weights = gpt_weights(seed=0)
    _, draft = gpt_weights(seed=2)
    monitor = Events()
    eng = engine(weights, cfg, monitor=monitor, speculate_k=2,
                 draft_weights=draft, draft_cfg=cfg)
    assert eng.draft_weights.wte.dtype == BF16
    assert eng.draft_weights.lnf_w is draft.lnf_w
    assert [e["tree"] for e in monitor.named("weights_held")] \
        == ["target", "draft"]
    assert eng.router_snapshot()["weight_bytes"] \
        == nbytes(eng.weights) + nbytes(eng.draft_weights)
    tokens = served_tokens(eng)
    # speculation is exact: the draft changes the schedule, not a token
    assert tokens == served_tokens(engine(weights, cfg))
    eng.done.clear()
    _, draft2 = gpt_weights(seed=3)
    eng.swap_weights(weights, draft_weights=draft2)
    assert eng.draft_weights.layers[0].fc1_k.dtype == BF16
    assert eng.draft_weights.layers[0].ln2_b is draft2.layers[0].ln2_b
