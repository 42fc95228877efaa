"""The serving model's second family (``rope_moe``: RMSNorm, rotary
positions, grouped-query heads with a per-head gate, window and full
attention mixed, a dropless top-k mixture of experts beside a shared
expert) against the plain float32 reference of the configuration that
uses it (``benchmarks/reference_laguna.py``, which shares no code with
``apex_tpu``), at tiny sizes on the CPU: **logits are compared, never
tokens**, through the same step functions, paged cache and kernels
(interpreted here) that the engine runs.

The tiny model keeps the published structure: five layers in the
published order (a dense full-attention layer, three windowed MoE
layers, a full MoE layer), 2 cache heads of 16 under 4 (full) and 6
(windowed) query heads, window 8, pages of 4, 8 experts of width 32
with 2 a token, rotary with the published YaRN parameters.  Contexts
run to 3-5 windows, so every windowed layer reads past its window and
across page boundaries.

Tolerances, each with its reason, are at their use.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from apex_tpu.ops.flash_decode import (flash_decode, flash_decode_multi,
                                       paged_attention_multi_reference,
                                       paged_attention_reference)
from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                              ServingModelConfig,
                              default_cache_config, gpt_sequence_logits,
                              init_cache, quantize_weights, rope_moe)
from apex_tpu.serving.model import (decode_logits, extend_logits,
                                    prefill_logits)
from benchmarks import builders_laguna, reference_laguna

WINDOW, BLOCK = 8, 4
TINY = dict(
    vocab_size=300, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    sliding_window=WINDOW, moe_routed_scaling_factor=2.5,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4])

# float32 against float32 through other code paths (kernels in blocks,
# online softmax, sorted experts): rounding alone.  The whole forward
# reads 2e-7 of logits whose spread is 0.16; anything structural -- a
# window edge off by one, a stale or foreign page, a dropped token, a
# missing norm weight -- moves a logit by 1e-3 or more.
F32_TOL = 2e-5


def model(dtype=jnp.float32, seed=1, **kw):
    cfg = builders_laguna.serving_config(TINY, max_seq=64, dtype=dtype,
                                         **kw)
    return cfg, builders_laguna.make_weights(TINY, cfg, seed)


def cache_for(cfg, num_blocks=40):
    ccfg = default_cache_config(cfg, num_blocks=num_blocks,
                                block_size=BLOCK, kv_dtype="model")
    return ccfg, init_cache(ccfg)


def tokens_of(n, seed=3):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         TINY["vocab_size"]))


def reference(weights, tokens):
    return np.asarray(reference_laguna.logits(weights, jnp.asarray(tokens),
                                              TINY))


def prefill(cfg, ccfg, cache, weights, tokens, blocks, pages):
    """Prefill ``tokens`` into ``blocks`` on a ``pages``-page rung."""
    pad = np.zeros(pages * BLOCK, np.int32)
    pad[:len(tokens)] = tokens
    table = np.zeros(pages, np.int32)
    table[:len(blocks)] = blocks
    return prefill_logits(weights, cfg, ccfg, cache, jnp.asarray(pad),
                          jnp.int32(len(tokens)), jnp.asarray(table))


class TestAgainstTheReference:
    def test_whole_sequence_forward(self):
        cfg, w = model()
        toks = tokens_of(40)
        got = gpt_sequence_logits(w, cfg, jnp.asarray(toks)[None])[0]
        assert np.abs(np.asarray(got) - reference(w, toks)).max() < F32_TOL

    @pytest.mark.parametrize("decode_attention", ["kernel", "reference"])
    def test_prefill_then_decode_through_the_paged_cache(
            self, decode_attention):
        """A prompt of 2.6 windows, then 14 decode steps to 4.4 windows
        and over three page boundaries, beside a second, shorter row
        and an inactive one: every step's logits are the reference's
        full forward at that position."""
        cfg, w = model(decode_attention=decode_attention)
        ccfg, cache = cache_for(cfg)
        toks, other = tokens_of(35), tokens_of(11, seed=5)
        want, want_other = reference(w, toks), reference(w, other)
        # scattered, interleaved block ids: no spatial locality assumed
        blocks = np.array([7, 3, 22, 9, 15, 30, 4, 11, 26])
        blocks_other = np.array([5, 17, 2])
        n0 = 21
        cache, last = prefill(cfg, ccfg, cache, w, toks[:n0],
                              blocks[:-(-n0 // BLOCK)], 8)
        assert np.abs(np.asarray(last) - want[n0 - 1]).max() < F32_TOL
        cache, last = prefill(cfg, ccfg, cache, w, other[:5],
                              blocks_other[:2], 8)
        assert np.abs(np.asarray(last) - want_other[4]).max() < F32_TOL
        for step in range(14):
            rows = [(toks, n0 + step, blocks)]
            if 5 + step < len(other):
                rows.append((other, 5 + step, blocks_other))
            table = np.zeros((4, 16), np.int32)
            args = np.zeros((5, 4), np.int32)   # tok, pos, len, blk, off
            for i, (seq, p, blk) in enumerate(rows):
                table[i, :len(blk)] = blk
                args[:, i] = (seq[p], p, p + 1, blk[p // BLOCK], p % BLOCK)
            cache, logits, counters = decode_logits(
                w, cfg, ccfg, cache, jnp.asarray(args[0]),
                jnp.asarray(args[1]), jnp.asarray(table),
                jnp.asarray(args[2]), jnp.asarray(args[3]),
                jnp.asarray(args[4]))
            for i, (seq, p, _) in enumerate(rows):
                ref = want if seq is toks else want_other
                assert np.abs(np.asarray(logits[i]) - ref[p]).max() \
                    < F32_TOL, (step, i)
            hit, most = (int(c) for c in counters)
            # 4 MoE layers, top-2, live rows only
            assert 2 * 4 <= hit <= 2 * 4 * len(rows)
            assert 4 <= most <= 4 * len(rows)

    @pytest.mark.parametrize("decode_attention", ["kernel", "reference"])
    def test_chunked_prefill_through_extend(self, decode_attention):
        """The same prompt in chunks of 8 (front-padded where short),
        two rows at once: windowed layers read earlier chunks through
        the cache, behind and across their window's edge."""
        cfg, w = model(decode_attention=decode_attention)
        ccfg, cache = cache_for(cfg)
        seqs = [tokens_of(37), tokens_of(22, seed=9)]
        wants = [reference(w, s) for s in seqs]
        blocks = [np.array([7, 3, 22, 9, 15, 30, 4, 11, 26, 13]),
                  np.array([5, 17, 2, 31, 8, 12])]
        done = [0, 0]
        t = 8
        while any(d < len(s) for d, s in zip(done, seqs)):
            toks = np.zeros((2, t), np.int32)
            wb = np.zeros((2, t), np.int32)
            wo = np.zeros((2, t), np.int32)
            table = np.zeros((2, 16), np.int32)
            lens = np.zeros(2, np.int32)
            took = [0, 0]
            for i, s in enumerate(seqs):
                n = min(t - (3 if done[i] == 0 else 0), len(s) - done[i])
                took[i] = n              # first chunk short: front pad
                if n == 0:
                    continue
                table[i, :len(blocks[i])] = blocks[i]
                lens[i] = done[i] + n
                for j in range(n):
                    p = done[i] + j
                    toks[i, t - n + j] = s[p]
                    wb[i, t - n + j] = blocks[i][p // BLOCK]
                    wo[i, t - n + j] = p % BLOCK
            cache, logits = extend_logits(
                w, cfg, ccfg, cache, jnp.asarray(toks), jnp.asarray(table),
                jnp.asarray(lens), jnp.asarray(wb), jnp.asarray(wo))
            for i, n in enumerate(took):
                if n:
                    got = np.asarray(logits[i, t - n:])
                    want = wants[i][done[i]:done[i] + n]
                    assert np.abs(got - want).max() < F32_TOL, (i, done)
                    done[i] += n

    @pytest.mark.parametrize("t", [3, 8])
    def test_chunks_leave_the_cache_a_prefill_leaves(self, t):
        """The page write of the extend step, chunks of 3 (several
        tokens on a page of 4, every other chunk across an edge) and of
        8 (three pages a chunk once the front-padded first chunk has
        shifted them), against the prefill's whole-page scatter: the same
        rows in the same slots of every layer's k and v."""
        cfg, w = model(decode_attention="reference")
        ccfg, empty = cache_for(cfg)
        assert len(empty.k) == len(empty.v) == TINY["num_hidden_layers"]
        assert all(a.shape == (40, 2, BLOCK, 16) for a in empty.k + empty.v)
        seq = tokens_of(22, seed=5)
        blocks = np.array([5, 17, 2, 31, 8, 12])
        whole, _ = prefill(cfg, ccfg, empty, w, seq, blocks, 8)
        cache = cache_for(cfg)[1]
        table = np.zeros((1, 8), np.int32)
        table[0, :len(blocks)] = blocks
        done = 0
        while done < len(seq):
            n = min(t - (1 if done == 0 else 0), len(seq) - done)
            toks, wb, wo = (np.zeros((1, t), np.int32) for _ in range(3))
            for j in range(n):
                p = done + j
                toks[0, t - n + j] = seq[p]
                wb[0, t - n + j] = blocks[p // BLOCK]
                wo[0, t - n + j] = p % BLOCK
            done += n
            cache, _ = extend_logits(
                w, cfg, ccfg, cache, jnp.asarray(toks), jnp.asarray(table),
                jnp.asarray([done], jnp.int32), jnp.asarray(wb),
                jnp.asarray(wo))
        rows = np.arange(len(seq))
        for got, want in zip(cache.k + cache.v, whole.k + whole.v):
            got, want = np.asarray(got), np.asarray(want)
            # (tokens, heads, d) of the prompt's slots, in position order
            g = got[blocks[rows // BLOCK], :, rows % BLOCK]
            x = want[blocks[rows // BLOCK], :, rows % BLOCK]
            assert np.abs(x).max() > 0.01
            np.testing.assert_allclose(g, x, rtol=0, atol=1e-5)
            # and no page that is not the prompt's (or the dump) touched
            others = np.setdiff1d(np.arange(1, 40), blocks)
            assert not got[others].any()

    def test_a_window_edge_off_by_one_fails_the_tolerance(self):
        """The tolerance has teeth: the same forward with the window one
        key short or one key long is far outside it."""
        cfg, w = model()
        toks = tokens_of(40)
        want = reference(w, toks)
        for wrong in (WINDOW - 1, WINDOW + 1):
            layers = tuple(dataclasses.replace(s, window=wrong)
                           if s.window else s for s in cfg.layers)
            got = gpt_sequence_logits(
                w, dataclasses.replace(cfg, layers=layers),
                jnp.asarray(toks)[None])[0]
            assert np.abs(np.asarray(got) - want).max() > 50 * F32_TOL

    def test_bf16_forward_is_within_its_rounding_and_fp8_weights_are_not(
            self):
        """The model dtype the cell runs, bf16 (weights and matmul
        inputs; float32 accumulation, residual stream and router),
        against the float32 reference on the same bf16 weights.
        Positions whose routing is decided by less than bf16's noise
        are compared apart: the reference reports each position's
        smallest score gap between the last selected and the first
        unselected expert, and where that gap is tiny a bf16 forward
        may rightly pick the other expert, which moves the logits by a
        whole expert's output.  Measured here over six seeds of 48
        positions (spread of the logits 0.16): expert sets differed
        only at gaps of 2.1e-4 and less (5 of 288 positions, logits off
        by 0.05-0.08 there, half a spread), every other position read
        0.0008-0.0021.  So ``GAP`` is 1e-3 (5x the widest gap that
        swapped; 75-85% of the positions lie above it) and ``BF16_TOL``
        1e-2 (5x the largest clean difference).  Weights rounded once
        more, to float8_e4m3 (less than bf16), read 0.022 at their best
        position and 0.03 at the median, so computing in less than the
        stated precision fails."""
        BF16_TOL, GAP = 1e-2, 1e-3
        for seed in range(4):
            cfg, w = model(jnp.bfloat16, seed=seed)
            toks = tokens_of(48, seed=seed)
            got = np.asarray(gpt_sequence_logits(
                w, cfg, jnp.asarray(toks)[None])[0])
            want = reference(w, toks)
            _, _, gaps = reference_laguna.margins(
                w, jnp.asarray(toks)[None], jnp.asarray(toks)[None], TINY)
            clean = np.asarray(gaps)[0] >= GAP
            assert clean.mean() > 0.5
            diff = np.abs(got - want).max(-1)
            assert diff[clean].max() < BF16_TOL, diff[clean].max()
            # a swapped expert is bounded too: by the spread of the logits
            assert diff.max() < want.std(-1).mean()
            coarse = jax.tree.map(
                lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                if x.dtype == jnp.bfloat16 else x, w)
            worse = np.asarray(gpt_sequence_logits(
                coarse, cfg, jnp.asarray(toks)[None])[0])
            assert np.abs(worse - want).max(-1)[clean].max() > 2 * BF16_TOL


class TestKernels:
    def cache(self, kv=2, d=16, nb=48, seed=0):
        k = jax.random.normal(jax.random.PRNGKey(seed), (nb, kv, BLOCK, d))
        v = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (nb, kv, BLOCK, d))
        lens = np.array([37, 9, 0, 21], np.int32)
        table = np.random.default_rng(seed).permutation(
            np.arange(1, nb))[:4 * 10].reshape(4, 10).astype(np.int32)
        for i, n in enumerate(lens):
            table[i, -(-n // BLOCK):] = 0
        return k, v, table, lens

    @pytest.mark.parametrize("groups,window", [(2, None), (3, 8), (3, 5),
                                               (1, 8)])
    def test_grouped_decode_kernel_with_a_window(self, groups, window):
        """Against the dense twin widened to groups and a window; the
        pages behind the window are then poisoned (NaN values, huge
        keys) and the output may not change by a bit: they are neither
        weighted nor computed."""
        k, v, table, lens = self.cache()
        q = jax.random.normal(jax.random.PRNGKey(9), (4, 2 * groups, 16))
        args = (jnp.asarray(table), jnp.asarray(lens))
        out = flash_decode(q, k, v, *args, window=window)
        want = paged_attention_reference(q, k, v, *args, window=window)
        # float32 online softmax against a dense softmax
        assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-5
        assert (np.asarray(out[2]) == 0).all()          # inactive row
        if window is None:
            return
        k2, v2 = np.array(k), np.array(v)
        for i, n in enumerate(lens):
            for page in table[i, :max(n - window, 0) // BLOCK]:
                k2[page], v2[page] = 1e6, np.nan
        again = flash_decode(q, jnp.asarray(k2), jnp.asarray(v2), *args,
                             window=window)
        assert (np.asarray(again) == np.asarray(out)).all()

    @pytest.mark.parametrize("groups,window", [(3, 8), (2, None), (3, 5)])
    def test_grouped_multi_token_kernel_with_a_window(self, groups, window):
        k, v, table, lens = self.cache(seed=4)
        q = jax.random.normal(jax.random.PRNGKey(9), (4, 6, 2 * groups, 16))
        args = (jnp.asarray(table), jnp.asarray(lens))
        out = flash_decode_multi(q, k, v, *args, window=window)
        want = paged_attention_multi_reference(q, k, v, *args,
                                               window=window)
        assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-5

    @pytest.mark.parametrize("s,bq,bk,window", [
        (40, 1024, 1024, 8),        # one block
        (300, 64, 128, 50),         # a band narrower than a key block
        (300, 64, 128, None),       # groups alone
        (512, 128, 128, 130)])      # a band over two key blocks
    def test_prefill_kernel_with_groups_and_a_window(self, s, bq, bk,
                                                     window):
        ks = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(ks[0], (1, 6, s, 16))
        k = jax.random.normal(ks[1], (1, 2, s, 16))
        v = jax.random.normal(ks[2], (1, 2, s, 16))
        out = flash_attention(q, k, v, causal=True, window=window,
                              block_q=bq, block_k=bk)
        want = mha_reference(q, k, v, causal=True, window=window)
        assert np.abs(np.asarray(out) - np.asarray(want)).max() < 1e-5

    def test_the_mask_the_references_share_is_the_stated_one(self):
        """``mha_reference``'s window, by hand: query 20 sees keys
        13..20 under a window of 8."""
        s = 24
        q = jnp.zeros((1, 1, s, 4))
        v = jnp.eye(s)[None, None]                 # value j marks key j
        out = mha_reference(q, q, v, causal=True, window=8)
        assert np.flatnonzero(np.asarray(out[0, 0, 20]) > 0).tolist() \
            == list(range(13, 21))


class TestRotary:
    def test_yarn_frequencies_against_float64(self):
        """The blend by hand in float64: with theta 500,000, 64 rotary
        dims, factor 64, 4,096 original positions, beta 64 / 1, the ramp
        runs from pair 5 to pair 16 (the pairs whose wavelengths make
        64 turns and 1 turn in 4,096 positions lie at 5.6 and 15.8)."""
        spec = builders_laguna.serving_config(
            TINY | {"head_dim": 128}, max_seq=64,
            dtype=jnp.float32).layers[0].rope
        assert spec.rotary_dim == 64
        got = np.asarray(rope_moe.rope_inv_freq(spec), np.float64)
        i = np.arange(32, dtype=np.float64)
        plain = 500000.0 ** (-2 * i / 64)
        ramp = np.clip((i - 5) / (16 - 5), 0, 1)
        want = plain / 64 * ramp + plain * (1 - ramp)
        assert np.abs(got / want - 1).max() < 1e-6
        assert got[4] == pytest.approx(plain[4]) \
            and got[20] == pytest.approx(plain[20] / 64)

    @pytest.mark.parametrize("kind", ["full_attention",
                                      "sliding_attention"])
    def test_rotation_against_the_reference_beyond_4096(self, kind):
        """Both implementations at positions on either side of the
        original 4,096 and far beyond.  float32 angles: a position of
        2e5 times a frequency near 1 carries 2e5 * 2^-24 = 0.012 rad of
        rounding in the product, the same in both, so they are compared
        with each other to 1e-5 and with float64 to that rounding."""
        cfg = builders_laguna.serving_config(TINY, max_seq=64,
                                             dtype=jnp.float32)
        spec = cfg.layers[0 if kind == "full_attention" else 1].rope
        pos = np.array([0, 1, 511, 4095, 4096, 4097, 8703, 200000])
        x = jax.random.normal(jax.random.PRNGKey(0), (len(pos), 3, 16))
        got = np.asarray(rope_moe.apply_rope(x, jnp.asarray(pos), spec))
        want = np.asarray(reference_laguna.rotate(
            x, jnp.asarray(pos), TINY["rope_parameters"][kind]))
        assert np.abs(got - want).max() < 1e-5
        freqs = np.asarray(rope_moe.rope_inv_freq(spec), np.float64)
        ang = pos[:, None, None] * freqs
        rot = spec.rotary_dim
        x64 = np.asarray(x, np.float64)
        lo, hi = x64[..., :rot // 2], x64[..., rot // 2:rot]
        exact = np.concatenate(
            [lo * np.cos(ang) - hi * np.sin(ang),
             hi * np.cos(ang) + lo * np.sin(ang), x64[..., rot:]], -1) \
            * np.concatenate([np.full(rot, spec.attention_factor),
                              np.ones(16 - rot)])
        # the unrotated tail is not scaled
        exact[..., rot:] = x64[..., rot:]
        assert np.abs(got - exact)[:7].max() < 2e-3
        assert np.abs(got - exact).max() < 0.1


class TestRouting:
    def layer(self, seed=0):
        cfg, w = model(seed=seed)
        return cfg, w.layers[1]

    @pytest.mark.parametrize("tokens", [5, 24, 64, 256, 257])
    def test_every_token_to_the_same_experts_drops_nothing(self, tokens):
        """All-positive inputs and router columns that are multiples of
        the all-ones vector send EVERY token to experts 7 and 6: the
        most uneven routing there is (a capacity-factor layer would drop
        all but ``capacity`` of them).  The sorted grouped matmuls, at
        a decode batch's rows and at a prefill's, equal the reference,
        which computes all experts for all tokens."""
        cfg, lw = self.layer()
        m = jnp.abs(jax.random.normal(jax.random.PRNGKey(tokens),
                                      (tokens, 64))) + 0.1
        router = jnp.ones((64, 1)) * jnp.arange(1, 9)[None, :] * 0.01
        lw = lw._replace(router=router)
        got, counters = rope_moe.mlp(m, lw, cfg,
                                     live=jnp.ones((tokens,), bool))
        assert [int(c) for c in counters] == [2, tokens]
        combine, _ = reference_laguna.routing(m, router, 2, 2.5)
        assert (np.asarray(combine)[:, :6] == 0).all() \
            and (np.asarray(combine)[:, 6:] > 0).all()
        with jax.default_matmul_precision("highest"):
            want = reference_laguna.experts(m, lw, combine) \
                + reference_laguna.swiglu(m, lw.s1, lw.s3, lw.s2)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5

    def test_random_routing_equals_every_expert_weighted_in(self):
        """Random routing (experts with no row among them): the grouped
        matmuls over the sorted pairs against the reference's pass over
        every expert, the unselected weighted by zero."""
        cfg, lw = self.layer(seed=2)
        m = jax.random.normal(jax.random.PRNGKey(1), (40, 64))
        weights, ids = rope_moe.route(m, lw.router, 2, 2.5)
        combine, _ = reference_laguna.routing(m, lw.router, 2, 2.5)
        with jax.default_matmul_precision("highest"):
            want = reference_laguna.experts(m, lw, combine)
        grouped = rope_moe._experts_sorted(m, lw, weights, ids)
        assert np.abs(np.asarray(want) - np.asarray(grouped)).max() < 1e-6
        assert np.asarray(weights).sum(-1) == pytest.approx(2.5, rel=1e-6)

    def test_counters_leave_the_dead_rows_out(self):
        ids = jnp.asarray([[0, 1], [1, 2], [5, 6]])
        live = jnp.asarray([True, True, False])
        assert [int(c) for c in rope_moe.moe_counters(ids, live, 8)] \
            == [3, 2]


class TestConfigAndRefusals:
    def test_head_size_and_cache_heads_are_stated(self):
        gpt = ServingModelConfig(vocab_size=100, hidden_size=64,
                                 num_heads=4, num_layers=2, max_seq=32)
        assert (gpt.head_dim, gpt.num_kv_heads, gpt.family) \
            == (16, 4, "gpt2")
        assert default_cache_config(gpt, num_blocks=9, block_size=4,
                                    kv_dtype="model").num_heads == 4
        cfg, _ = model()
        # 4 query heads of 16 on a hidden of 64 would derive 16 too;
        # the cache heads would not: they are the config's word
        assert (cfg.head_dim, cfg.num_kv_heads) == (16, 2)
        ccfg = default_cache_config(cfg, num_blocks=9, block_size=4,
                                    kv_dtype="model")
        assert (ccfg.num_heads, ccfg.head_dim) == (2, 16)
        with pytest.raises(ValueError, match="not divisible"):
            ServingModelConfig(vocab_size=100, hidden_size=64, num_heads=5,
                               num_layers=2, max_seq=32)
        # stated, a head size need not divide the hidden size
        ServingModelConfig(vocab_size=100, hidden_size=64, num_heads=5,
                           num_layers=2, max_seq=32, head_dim=16)

    def test_what_cannot_serve_the_family_says_so(self):
        from apex_tpu.serving import EPContext, TPContext

        cfg, w = model()
        ccfg, _ = cache_for(cfg)
        for context in (TPContext, EPContext):
            with pytest.raises(ValueError, match="rope_moe"):
                context(cfg, ccfg, 2)
        with pytest.raises(ValueError, match="rope_moe"):
            quantize_weights(w)
        int8 = dataclasses.replace(ccfg, kv_dtype="int8")
        with pytest.raises(ValueError, match="rope_moe"):
            ServingEngine(w, cfg, int8,
                          ladder=BucketLadder(batch=(2,), pages=(8,)))
        with pytest.raises(ValueError, match="LayerSpec"):
            dataclasses.replace(cfg, layers=cfg.layers[:2])


class TestThroughTheEngine:
    @pytest.mark.parametrize("chunk", [0, 8])
    def test_engine_serves_the_family_and_counts_its_ticks(self, chunk):
        """Through ``ServingEngine`` (scheduler, ladder, cache manager),
        whole-prompt and chunked prefill: in float32 every emitted
        token is the reference's arg-max (margin 0), and the decode
        ticks' counters arrive on the ``decode_step`` event and, while
        a tracer records, in the running sums."""
        from apex_tpu.monitor import tracing

        events = []

        class Monitor:
            def event(self, kind, name, value=None, **attrs):
                if name == "decode_step":
                    events.append(attrs)

        cfg, w = model()
        ccfg, _ = cache_for(cfg)
        engine = ServingEngine(
            w, cfg, ccfg, ladder=BucketLadder(batch=(2, 4), pages=(8, 16)),
            monitor=Monitor(), prefill_chunk=chunk, prefix_share=False,
            speculate_k=0, slo=None)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=f"r{i}", prompt=list(
            rng.integers(0, 300, size=n)), max_new_tokens=m)
            for i, (n, m) in enumerate([(30, 20), (5, 12), (17, 30)])]
        for r in reqs:
            engine.submit(r)
        while not events:           # to the first decode tick, unrecorded
            engine.step()
        assert not engine.tick_sums
        events.clear()
        tracing.set_tracer(tracing.SpanTracer())
        try:
            while engine.queue or engine.active or engine.prefilling:
                engine.step()
        finally:
            tracing.set_tracer(None)
        for r in reqs:
            seq = list(r.prompt) + list(r.out_tokens)
            want = reference(w, np.asarray(seq[:-1]))[len(r.prompt) - 1:]
            chosen = want[np.arange(len(r.out_tokens)), r.out_tokens]
            assert (want.max(-1) - chosen).max() == 0.0
        sums = engine.tick_sums
        assert sums["ticks"] == len(events)
        for key in ("rows", "experts_hit", "expert_max_rows", "pages_full",
                    "pages_window", "pages_dead", "tokens_full",
                    "tokens_window"):
            assert sums[key] == sum(e[key] for e in events) > 0, key
        assert sums["rows"] == sum(e["batch"] for e in events)
        # windowed layers hold every page of a sequence; what they read
        # and what lies dead behind the window add up to that, 3 layers
        # of them against 2 full
        assert 2 * (sums["pages_window"] + sums["pages_dead"]) \
            == 3 * sums["pages_full"]
