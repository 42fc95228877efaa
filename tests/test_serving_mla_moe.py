"""The serving model's third family, ``mla_moe``, at a tiny size with the
published structure (a dense layer, four MoE layers that hold 4 of 16
experts, an MTP module; q and kv ranks, position-free and rotary head
dims, a V width unequal to the QK width), against the plain reference
``benchmarks/reference_openpangu.py``.

Each comparison states its tolerance beside two readings: what the
float32 program gives (rounding of a different summation order) and
what a forward one precision lower would give, which has to fail.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serving
from apex_tpu.ops import latent_decode as ld
from apex_tpu.serving import mla_moe, model as sm, rope_moe
from benchmarks import builders_openpangu, reference_openpangu

CONFIG = {
    "vocab_size": 160, "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 25600000,
    "routed_scaling_factor": 2.5,
    "deployment_share": {"router_outputs": 16, "expert_first": 4,
                         "experts_held": 4}}
BLOCK, PAGES, BLOCKS = 4, 8, 33
# float32 against float32-highest: summation order alone.  Readings:
# 3e-6 at most over every comparison below; the same forward with its
# matrices rounded to bf16 reads 2e-3 to 2e-2 (test_bf16_...).
F32_TOL = 1e-4


def build(dtype=jnp.float32, mtp=False, seed=3, **cfg_overrides):
    cfg = builders_openpangu.serving_config(
        CONFIG, max_seq=PAGES * BLOCK, dtype=dtype, mtp=mtp)
    cfg = dataclasses.replace(cfg, **cfg_overrides)
    return cfg, builders_openpangu.make_weights(CONFIG, cfg, seed)


def cache_for(cfg):
    ccfg = serving.default_cache_config(cfg, num_blocks=BLOCKS,
                                        block_size=BLOCK, kv_dtype="model")
    return ccfg, serving.init_cache(ccfg)


TOKENS = np.random.default_rng(0).integers(0, 160, 23).astype(np.int32)


def served_logits(cfg, weights, tokens, prompt_len):
    """Prefill ``prompt_len`` tokens, then decode the rest one at a time
    through the paged latent cache: the logits after each position from
    ``prompt_len - 1`` on, (len - prompt_len + 1, V)."""
    ccfg, cache = cache_for(cfg)
    blocks = np.arange(1, PAGES + 1, dtype=np.int32)     # dump page 0
    padded = np.zeros(PAGES * BLOCK, np.int32)
    padded[:prompt_len] = tokens[:prompt_len]
    cache, last = sm.prefill_logits(
        weights, cfg, ccfg, cache, jnp.asarray(padded),
        jnp.int32(prompt_len), jnp.asarray(blocks))
    out = [last]
    for p in range(prompt_len, len(tokens)):
        cache, lg, _ = sm.decode_logits(
            weights, cfg, ccfg, cache, jnp.asarray(tokens[p:p + 1]),
            jnp.asarray([p], jnp.int32), jnp.asarray(blocks[None]),
            jnp.asarray([p + 1], jnp.int32),
            jnp.asarray([blocks[p // BLOCK]]),
            jnp.asarray([p % BLOCK], jnp.int32))
        out.append(lg[0])
    return jnp.stack(out)


def test_config_is_the_latent_kind():
    cfg, weights = build()
    ccfg, cache = cache_for(cfg)
    # a row of 32 + 8 values, stored at a whole lane tile
    assert cfg.mla.latent_dim == 40
    assert (cfg.num_kv_heads, cfg.head_dim) == (1, 128)
    assert ccfg.latent and ccfg.value_dim == 32
    assert ccfg.kv_shape == (BLOCKS, 1, BLOCK, 128)
    assert cache.v is None and len(cache.k) == 5
    assert ccfg.cache_nbytes() == 5 * BLOCKS * BLOCK * 128 * 4
    assert weights.mtp is None
    assert weights.layers[0].router is None
    assert weights.layers[1].e1.shape == (4, 64, 32)
    assert weights.layers[1].router.shape == (64, 16)
    with pytest.raises(ValueError, match="no int8 storage"):
        serving.default_cache_config(cfg, num_blocks=BLOCKS,
                                     block_size=BLOCK, kv_dtype="int8")


@pytest.mark.parametrize("decode_attention", ["kernel", "reference"])
def test_prefill_then_decode_equals_the_reference(decode_attention):
    cfg, weights = build(decode_attention=decode_attention)
    want = reference_openpangu.logits(weights, jnp.asarray(TOKENS), CONFIG)
    got = served_logits(cfg, weights, TOKENS, prompt_len=9)
    np.testing.assert_allclose(got, want[8:], atol=F32_TOL, rtol=0)


def test_bf16_forward_is_told_from_float32():
    """The control of F32_TOL: the same weights through a bf16 program
    land two orders of magnitude outside it."""
    cfg, weights = build()
    want = reference_openpangu.logits(weights, jnp.asarray(TOKENS), CONFIG)
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    low_w = jax.tree.map(lambda w: w.astype(jnp.bfloat16)
                         if w.ndim > 1 and w.shape[-1] != 16 else w,
                         weights)
    got = served_logits(low, low_w, TOKENS, prompt_len=9)
    assert float(jnp.abs(got - want[8:]).max()) > 10 * F32_TOL


def test_absorbed_equals_expanded():
    """Decode and a 3-token extend (absorbed, paged) against the
    sequence forward (expanded, no cache)."""
    cfg, weights = build()
    whole = sm.gpt_sequence_logits(weights, cfg, jnp.asarray(TOKENS[None]))
    got = served_logits(cfg, weights, TOKENS, prompt_len=9)
    np.testing.assert_allclose(got, whole[0, 8:], atol=F32_TOL, rtol=0)
    ccfg, cache = cache_for(cfg)
    blocks = np.arange(1, PAGES + 1, dtype=np.int32)
    padded = np.zeros(PAGES * BLOCK, np.int32)
    padded[:9] = TOKENS[:9]
    cache, _ = sm.prefill_logits(weights, cfg, ccfg, cache,
                                 jnp.asarray(padded), jnp.int32(9),
                                 jnp.asarray(blocks))
    pos = np.arange(9, 12)
    cache, lg = sm.extend_logits(
        weights, cfg, ccfg, cache, jnp.asarray(TOKENS[None, 9:12]),
        jnp.asarray(blocks[None]), jnp.asarray([12], jnp.int32),
        jnp.asarray(blocks[pos // BLOCK][None]),
        jnp.asarray((pos % BLOCK)[None].astype(np.int32)))
    np.testing.assert_allclose(lg[0], whole[0, 9:12], atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("t", [1, 2, 5])
def test_kernel_interpreted_equals_twin(t):
    """Several page groups a row, a row that ends inside a page, an
    inactive row and, at t > 1, a front-padded chunk."""
    b, h, dl, dv, bs, mp, nb = 4, 4, 40, 32, 4, 16, 40
    key = jax.random.PRNGKey(t)
    q = jax.random.normal(key, (b, t, h, dl), jnp.float32)
    cache = jax.random.normal(jax.random.fold_in(key, 1),
                              (nb, 1, bs, dl), jnp.float32)
    lens = np.array([0, t - 1 if t > 1 else 1, 21, 64], np.int32)
    tables = np.zeros((b, mp), np.int32)
    free = iter(np.random.default_rng(t).permutation(np.arange(1, nb)))
    for i, n in enumerate(lens):
        for j in range(-(-int(n) // bs)):
            tables[i, j] = next(free)
    args = (cache, jnp.asarray(tables), jnp.asarray(lens))
    if t == 1:
        got = ld.latent_decode(q[:, 0], *args, value_dim=dv, scale=0.2)
        want = ld.latent_attention_reference(q[:, 0], *args, value_dim=dv,
                                             scale=0.2)
    else:
        got = ld.latent_decode_multi(q, *args, value_dim=dv, scale=0.2)
        want = ld.latent_attention_multi_reference(q, *args, value_dim=dv,
                                                   scale=0.2)
    # float32 both ways: the online softmax's order of sums (3e-7 read)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert not np.asarray(got[0]).any()            # the inactive row
    assert ld._pages_per_step(mp, bs) == 16        # 64 keys a step
    assert ld._pages_per_step(288, 16) == 8 and ld._pages_per_step(7, 16) == 7


def test_mtp_logits_equal_the_reference():
    cfg, weights = build(mtp=True)
    tokens = jnp.asarray(TOKENS)
    nxt = jnp.roll(tokens, -1)
    _, want = reference_openpangu.logits(weights, tokens, CONFIG,
                                         tokens_next=nxt)
    # the program's module on the program's own hidden states, expanded
    pos = jnp.arange(len(TOKENS), dtype=jnp.int32)[None]
    x = sm._embed(weights, tokens[None], pos, cfg)
    for i, lw in enumerate(weights.layers):
        out, _ = mla_moe.expanded(x, lw, cfg, cfg.layers[i], pos)
        x, _ = sm._layer_tail(x, lw, out, cfg)
    g = mla_moe.mtp_input(x, nxt[None], weights, cfg.layernorm_eps)
    out, _ = mla_moe.expanded(g, weights.mtp.layer, cfg, cfg.layers[-1],
                              pos)
    g, _ = sm._layer_tail(g, weights.mtp.layer, out, cfg)
    got = mla_moe.mtp_logits(g, weights, cfg.layernorm_eps)[0]
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def engine_for(speculate_k, seed=3):
    cfg, weights = build(mtp=speculate_k > 0, seed=seed)
    ccfg = serving.default_cache_config(cfg, num_blocks=BLOCKS,
                                        block_size=BLOCK, kv_dtype="model")
    return serving.ServingEngine(
        weights, cfg, ccfg,
        ladder=serving.BucketLadder(batch=(4,), pages=(PAGES,)),
        speculate_k=speculate_k, spec_governor=None, prefill_chunk=0,
        prefix_share=False, slo=None)


def run_requests(engine, n_new=9):
    rng = np.random.default_rng(1)
    reqs = [serving.Request(rid=f"r{i}", max_new_tokens=n_new - i,
                            prompt=[int(t) for t in
                                    rng.integers(0, 160, 5 + 3 * i)])
            for i in range(3)]
    for r in reqs:
        engine.submit(r)
    while engine.queue or engine.active:
        engine.step()
    return [r.out_tokens for r in reqs]


def test_speculation_by_the_models_own_mtp_emits_the_same_tokens():
    """Draft on = draft off, token for token; the module's proposals
    are counted, and with the model's own weights in the module's layer
    (h_t stands in for g_t badly: random weights) few are kept."""
    plain = engine_for(0)
    want = run_requests(plain)
    spec = engine_for(1)
    assert spec.cache_cfg.num_layers == 6 and len(spec.cache.k) == 6
    got = run_requests(spec)
    assert got == want
    assert [len(t) for t in got] == [9, 8, 7]
    assert spec.spec_proposed > 0
    assert 0 <= spec.spec_accepted <= spec.spec_proposed
    assert spec.summary().spec_accept_rate == pytest.approx(
        spec.spec_accepted / spec.spec_proposed, abs=1e-4)
    # every tick is one program: no draft model's programs were made
    assert not spec._draft_decode_exec and spec.draft_cache is None


def test_an_accepted_draft_advances_two_tokens_a_tick():
    """An MTP module that always proposes what the target will choose
    (the test plants the target's own next tokens as drafts) is accepted
    every time: the engine then emits the same tokens in about half the
    ticks, which exercises the kept second slot and the module's catch-up
    through it."""
    want = run_requests(engine_for(0))
    spec = engine_for(1)
    oracle = {f"r{i}": toks for i, toks in enumerate(want)}
    step = spec.step

    def planted():
        for rid, q in spec.active.items():
            n = len(q.out_tokens)
            if n < len(oracle[rid]):
                q.draft = oracle[rid][n]
        return step()

    spec.step = planted
    assert run_requests(spec) == want
    assert spec.spec_accepted >= spec.spec_proposed - 3
    assert spec.steps < sum(len(t) for t in want) / 2 + 3


def test_speculation_needs_a_draft_and_names_the_mtp_route():
    cfg, weights = build()
    ccfg, _ = cache_for(cfg)
    with pytest.raises(ValueError, match="multi-token-prediction"):
        serving.ServingEngine(weights, cfg, ccfg, speculate_k=1,
                              ladder=serving.BucketLadder(batch=(4,),
                                                          pages=(PAGES,)))
    cfg, weights = build(mtp=True)
    ccfg, _ = cache_for(cfg)
    with pytest.raises(ValueError, match="speculate_k=1"):
        serving.ServingEngine(weights, cfg, ccfg, speculate_k=2,
                              ladder=serving.BucketLadder(batch=(4,),
                                                          pages=(PAGES,)))


def test_the_sixteen_shares_of_one_moe_layer_add_up_to_the_uncut_layer():
    """One MoE layer's routed part computed by each of four chips that
    hold 4 of 16 experts (``expert_first`` 0, 4, 8, 12), summed, plus
    the shared expert ONCE, is the layer with all 16 experts held; and
    the held-share path's counters count held experts."""
    cfg, _ = build()
    key = jax.random.PRNGKey(5)
    full = rope_moe.init_rope_moe_weights(
        key, dataclasses.replace(
            cfg, family="rope_moe", mla=None, head_dim=16, num_kv_heads=4,
            layers=(serving.LayerSpec(num_heads=4, window=None, moe=True,
                                      rope=cfg.layers[0].rope),),
            num_layers=1),
        dense_ffn=96, expert_ffn=32, shared_ffn=32).layers[0]
    m = jax.random.normal(jax.random.fold_in(key, 9), (11, 64), jnp.float32)
    live = jnp.arange(11) < 9
    whole, counted = rope_moe.mlp(
        m, full, dataclasses.replace(cfg, expert_first=0), live)
    shared = rope_moe._swiglu(m, full.s1, full.s3, full.s2)
    total, hit, pairs = jnp.zeros_like(whole), 0, 0
    for first in range(0, 16, 4):
        part = full._replace(**{k: getattr(full, k)[first:first + 4]
                                for k in ("e1", "e3", "e2")})
        chip = dataclasses.replace(cfg, expert_first=first)
        out, counters = rope_moe.mlp(m, part, chip, live)
        assert counters.shape == (3,)
        total = total + out - shared
        hit, pairs = hit + int(counters[0]), pairs + int(counters[2])
    # float32, sixteen experts' sums in another order: 4e-7 read; one
    # expert's output left out or counted twice reads 1e-2
    np.testing.assert_allclose(total + shared, whole, atol=1e-5, rtol=0)
    assert counted.shape == (2,) and hit == int(counted[0])
    assert pairs == 9 * cfg.experts_per_token      # every live pair, once


def test_a_skewed_routing_drops_no_pair():
    """Every row on the one expert this chip holds: more pairs than its
    grouped matmuls take at a time, so the step takes them in four
    goes; five pairs take one."""
    cfg, weights = build()
    lw = weights.layers[1]
    lw = lw._replace(e1=lw.e1[:1], e3=lw.e3[:1], e2=lw.e2[:1])
    t, k = 64, cfg.experts_per_token
    assert rope_moe._held_rows(t * k, 1, 16) == 16
    assert rope_moe._held_rows(64 * 8, 16, 256) == 64      # the cell's tick
    m = jax.random.normal(jax.random.PRNGKey(2), (t, 64), jnp.float32)
    w = jnp.full((t, k), 0.5, jnp.float32)
    one = 0.5 * rope_moe._swiglu(m, lw.e1[0], lw.e3[0], lw.e2[0])
    for ids, rows in (([4, 9], slice(None)), ([3, 9], slice(0, 0))):
        ids = jnp.tile(jnp.asarray([ids]), (t, 1)).at[:5, 0].set(4)
        want = jnp.zeros_like(one).at[:5].set(one[:5]).at[rows].set(
            one[rows])
        got = rope_moe._experts_sorted(m, lw, w, ids, 4)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def _parents_experts_sorted(m, lw, weights, ids):
    """``rope_moe._experts_sorted`` as PR 29 wrote it, kept here as the
    oracle of 'a layer that holds every expert computes what it did'."""
    t, k = ids.shape
    e = lw.e1.shape[0]
    flat = ids.reshape(t * k)
    order = jnp.argsort(flat)
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    xs = m.astype(lw.e1.dtype)[order // k]
    gate = jax.lax.ragged_dot(xs, lw.e1, sizes,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, lw.e3, sizes,
                            preferred_element_type=jnp.float32)
    act = jax.nn.silu(gate) * up * weights.reshape(t * k)[order][:, None]
    out = jax.lax.ragged_dot(act.astype(lw.e2.dtype), lw.e2, sizes,
                             preferred_element_type=jnp.float32)
    return jnp.zeros((t, m.shape[-1]), jnp.float32).at[order // k].add(out)


def test_a_layer_that_holds_every_expert_is_bit_for_bit_the_parents():
    """Laguna's path: same values to the bit, and the same lowered
    program text."""
    cfg, _ = build()
    lw = rope_moe.init_rope_moe_weights(
        jax.random.PRNGKey(5), dataclasses.replace(
            cfg, family="rope_moe", mla=None, head_dim=16, num_kv_heads=4,
            layers=(serving.LayerSpec(num_heads=4, window=None, moe=True,
                                      rope=cfg.layers[0].rope),),
            num_layers=1, dtype=jnp.bfloat16),
        dense_ffn=96, expert_ffn=32, shared_ffn=32).layers[0]
    m = jax.random.normal(jax.random.PRNGKey(7), (13, 64), jnp.float32)
    weights, ids = rope_moe.route(m, lw.router, 2, 2.5)
    now = jax.jit(rope_moe._experts_sorted)
    then = jax.jit(_parents_experts_sorted)
    assert np.array_equal(now(m, lw, weights, ids),
                          then(m, lw, weights, ids))

    def body(text):
        return text[text.index("{"):].replace("_parents_experts_sorted",
                                              "_experts_sorted")

    assert body(now.lower(m, lw, weights, ids).as_text()) \
        == body(then.lower(m, lw, weights, ids).as_text())


def test_the_engine_counts_latent_pages_and_held_experts():
    from apex_tpu.monitor import tracing

    engine = engine_for(0)
    for i, n in enumerate((14, 6)):
        engine.submit(serving.Request(rid=f"r{i}", max_new_tokens=4,
                                      prompt=list(range(1, n))))
    engine.step()
    assert not engine.tick_sums        # a tick ran and nothing recorded
    tracing.set_tracer(tracing.SpanTracer())
    try:
        engine.step()
    finally:
        tracing.set_tracer(None)
    sums = engine.tick_sums
    # the second tick: sequences of 15 and 7 positions, pages of 4, five
    # latent layers; four MoE layers that hold 4 experts each
    assert sums["ticks"] == 1 and sums["rows"] == 2
    assert sums["latent_tokens"] == 5 * (15 + 7)
    assert sums["latent_pages"] == 5 * (4 + 2)
    assert sums["experts_slots"] == 4 * 4
    assert 0 <= sums["experts_hit"] <= sums["pairs_held"] <= 2 * 2 * 4
    assert sums["expert_max_rows"] <= 2 * 4


def test_what_refuses_the_family_says_so_by_name():
    from apex_tpu.ops.quant_matmul import quantize_weights

    cfg, weights = build()
    ccfg, _ = cache_for(cfg)
    with pytest.raises(ValueError, match="'mla_moe' has no tensor-parallel"
                                         ".*latent cache"):
        serving.TPContext(cfg, ccfg, 2)
    with pytest.raises(ValueError, match="'mla_moe' has no expert-parallel"
                                         ".*no exchange"):
        serving.EPContext(cfg, ccfg, 2)
    with pytest.raises(ValueError, match="'mla_moe'.*no Q8 layout"):
        quantize_weights(weights)
    with pytest.raises(ValueError, match="serves from the.*latent cache"):
        serving.ServingEngine(
            weights, cfg, serving.KVCacheConfig(
                num_layers=5, num_heads=1, head_dim=128, num_blocks=BLOCKS,
                block_size=BLOCK),
            ladder=serving.BucketLadder(batch=(4,), pages=(PAGES,)))
    with pytest.raises(ValueError, match="states.*its latent attention"):
        dataclasses.replace(cfg, family="rope_moe")
