"""Decode fast path tests (ISSUE-12): copy-on-write prefix sharing,
speculative decoding, and chunked prefill.

The three acceptance bars, each proven here rather than vibed:

* **speculative greedy decode is token-for-token identical** to the
  non-speculative engine — across self/narrow drafts, bucket shapes,
  admission interleaves, mid-window EOS, and token-budget caps;
* **CoW shared-block invariants** — refcounts never free a mapped
  block, appends never mutate a shared page (device bytes compared),
  evict/readmit hits warm through the idle LRU, and admission bills
  only the unshared tail;
* **chunked prefill keeps the compile ladder closed** — one compile
  per bucket under ``sanitize()`` with prefills spanning ticks, and
  running requests keep decoding while a long admission streams in.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.serving import (BucketLadder, CachePoolExhausted,
                              KVCacheConfig, KVCacheManager, Request,
                              ServingEngine, ServingModelConfig,
                              default_cache_config,
                              extract_serving_weights)
from apex_tpu.testing.standalone_gpt import GPTModel, serve_smoke


def _tiny_model(vocab=32, hidden=16, heads=2, layers=2, max_seq=64,
                seed=0):
    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_sequence_length=max_seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=jnp.float32)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _serving(model, params):
    cfg = ServingModelConfig.from_model(
        model, prefill_flash=False, decode_attention="reference")
    return cfg, extract_serving_weights(params, cfg.num_layers)


def _engine(model, params, *, ladder, num_blocks=32, block_size=4,
            **kw):
    cfg, weights = _serving(model, params)
    cache_cfg = default_cache_config(cfg, num_blocks=num_blocks,
                                     block_size=block_size)
    return ServingEngine(weights, cfg, cache_cfg, ladder=ladder, **kw)


def _run(eng, prompts, new_tokens=5, eos=None, staggered=False):
    reqs = [Request(rid=f"r{i}", prompt=list(p),
                    max_new_tokens=new_tokens, eos_token=eos)
            for i, p in enumerate(prompts)]
    if staggered:
        eng.submit(reqs[0])
        pending = reqs[1:]

        def drip(step):
            if pending:
                eng.submit(pending.pop(0))

        s = eng.run(before_tick=drip)
        while pending:
            eng.submit(pending.pop(0))
            s = eng.run()
    else:
        for r in reqs:
            eng.submit(r)
        s = eng.run()
    return s, {q.rid: q.out_tokens for q in eng.done}


PROMPTS = [[3, 7, 1], [11, 2, 9, 4, 5], [6, 6, 2, 1, 9, 8, 3], [4]]
LADDER = BucketLadder(batch=(2, 4), pages=(2, 4))


@pytest.fixture(scope="module")
def tiny():
    return _tiny_model()


@pytest.fixture(scope="module")
def baseline(tiny):
    """The non-speculative, non-shared, non-chunked oracle tokens."""
    model, params = tiny
    eng = _engine(model, params, ladder=LADDER)
    _, tokens = _run(eng, PROMPTS)
    return tokens


def _self_draft(model, params):
    cfg, weights = _serving(model, params)
    return dict(speculate_k=2, draft_weights=weights, draft_cfg=cfg)


def _narrow_draft():
    dm, dp = _tiny_model(hidden=16, heads=2, layers=1, seed=7)
    dcfg, dweights = _serving(dm, dp)
    return dict(draft_weights=dweights, draft_cfg=dcfg)


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculativeDecode:
    def test_self_draft_bitwise_and_full_acceptance(self, tiny,
                                                    baseline):
        # the target proposing for itself must accept every draft
        # token and still emit exactly the greedy stream — the
        # machinery ceiling: 1 + K tokens per tick
        model, params = tiny
        eng = _engine(model, params, ladder=LADDER,
                      **_self_draft(model, params))
        s, tokens = _run(eng, PROMPTS)
        assert tokens == baseline
        assert s.spec_accept_rate == 1.0
        assert s.spec_tokens_accepted == s.spec_tokens_proposed > 0
        # 5 tokens per request at 3/tick needs 2 ticks, not 4
        base_steps = _run(_engine(model, params, ladder=LADDER),
                          PROMPTS)[0].decode_steps
        assert s.decode_steps < base_steps

    def test_narrow_draft_bitwise_with_rejections(self, tiny,
                                                  baseline):
        # a disagreeing draft exercises the rollback path: rejected
        # tokens roll the KV cursor back, output stays identical
        model, params = tiny
        eng = _engine(model, params, ladder=LADDER, speculate_k=2,
                      **_narrow_draft())
        s, tokens = _run(eng, PROMPTS)
        assert tokens == baseline
        assert s.spec_accept_rate is not None \
            and s.spec_accept_rate < 1.0

    @pytest.mark.parametrize("k", [1, 3])
    def test_speculate_k_grid(self, tiny, baseline, k):
        model, params = tiny
        kw = _self_draft(model, params)
        kw["speculate_k"] = k
        eng = _engine(model, params, ladder=LADDER, **kw)
        _, tokens = _run(eng, PROMPTS)
        assert tokens == baseline

    def test_bitwise_across_bucket_shapes(self, tiny, baseline):
        model, params = tiny
        fat = BucketLadder(batch=(8,), pages=(2, 4, 8))
        eng = _engine(model, params, ladder=fat, num_blocks=64,
                      **_self_draft(model, params))
        _, tokens = _run(eng, PROMPTS)
        assert tokens == baseline

    def test_bitwise_across_admission_interleave(self, tiny,
                                                 baseline):
        model, params = tiny
        eng = _engine(model, params, ladder=LADDER,
                      **_self_draft(model, params))
        _, tokens = _run(eng, PROMPTS, staggered=True)
        assert tokens == baseline

    def test_eos_mid_window_truncates(self, tiny, baseline):
        # pick an EOS that the oracle emits mid-stream, so under
        # K=2 speculation it lands inside an accepted window: the
        # emission (and the KV cursor) must truncate at it exactly
        # like the plain engine's per-token EOS check
        model, params = tiny
        eos = baseline["r1"][2]                 # 3rd emitted token
        plain = _engine(model, params, ladder=LADDER)
        _, want = _run(plain, PROMPTS, eos=eos)
        spec = _engine(model, params, ladder=LADDER,
                       **_self_draft(model, params))
        _, got = _run(spec, PROMPTS, eos=eos)
        assert got == want
        assert got["r1"][-1] == eos and len(got["r1"]) == 3

    def test_token_budget_cap_mid_window(self, tiny):
        # max_new_tokens not a multiple of K+1: the final tick may
        # emit fewer than K+1 tokens and must stop exactly at budget
        model, params = tiny
        plain = _engine(model, params, ladder=LADDER)
        _, want = _run(plain, PROMPTS, new_tokens=4)
        spec = _engine(model, params, ladder=LADDER,
                       **_self_draft(model, params))
        s, got = _run(spec, PROMPTS, new_tokens=4)
        assert got == want
        assert all(len(t) == 4 for t in got.values())

    def test_speculate_requires_draft(self, tiny):
        model, params = tiny
        with pytest.raises(ValueError, match="draft"):
            _engine(model, params, ladder=LADDER, speculate_k=2)

    def test_summary_reports_acceptance(self, tiny):
        # satellite: ServeSummary carries printed numbers, and the
        # serve_tick gauges carry the per-window acceptance feed
        model, params = tiny
        events = []

        class Sink:
            def event(self, kind, name, **kw):
                events.append((kind, name, kw))

        eng = _engine(model, params, ladder=LADDER, monitor=Sink(),
                      **_self_draft(model, params))
        s, _ = _run(eng, PROMPTS)
        assert s.spec_tokens_proposed > 0
        d = s.as_dict()
        assert d["spec_accept_rate"] == 1.0
        ticks = [kw for k, n, kw in events if k == "serve_tick"]
        assert any(kw.get("spec_proposed") for kw in ticks)
        assert any(kw.get("spec_accept_rate") == 1.0 for kw in ticks)


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing
# ---------------------------------------------------------------------------

SYS = [9, 8, 7, 6, 5, 4, 3, 2, 1, 2, 3]     # the "system prompt"


def _share_engine(model, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("ladder", BucketLadder(batch=(2, 4), pages=(4, 8)))
    return _engine(model, params, prefix_share=True, **kw)


class TestPrefixSharingManager:
    CFG = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                        num_blocks=10, block_size=4)

    def test_register_match_and_chain_miss(self):
        m = KVCacheManager(self.CFG, prefix_sharing=True)
        prompt = list(range(10))            # 2 full blocks + 2 tail
        blocks = m.alloc("a", 10)
        assert m.register_prefix("a", prompt) == 3
        hit = m.match_prefix(prompt)
        assert hit.blocks == tuple(blocks) and hit.cow \
            and hit.tokens == 9             # full hit leaves 1 tail
        part = m.match_prefix(prompt[:8] + [99, 98])
        assert part.blocks == tuple(blocks[:2]) \
            and part.tokens == 8 and not part.cow
        # a different FIRST block kills the whole chain
        assert not m.match_prefix([99] + prompt[1:]).warm

    def test_no_free_while_shared(self):
        m = KVCacheManager(self.CFG, prefix_sharing=True)
        prompt = list(range(8))
        blocks = m.alloc("a", 8)
        m.register_prefix("a", prompt)
        hit = m.match_prefix(prompt + [7])  # 2 full blocks warm
        m.alloc("b", 9, shared_blocks=hit.blocks)
        m.free("a")
        # b still maps both: neither block may re-enter the pool
        assert all(blk not in m._free for blk in blocks)
        assert m._refs[blocks[0]] == 1
        m.free("b")
        # zero refs parks them idle (cached), still off the free list
        assert all(blk not in m._free for blk in blocks)
        assert m.idle_blocks == 2
        assert m.match_prefix(prompt + [7]).warm   # still hits warm

    def test_append_into_shared_page_guarded(self):
        m = KVCacheManager(self.CFG, prefix_sharing=True)
        prompt = list(range(6))             # 1 full + partial(2)
        m.alloc("a", 6)
        m.register_prefix("a", prompt)
        with pytest.raises(RuntimeError, match="shared page"):
            m.append("a")                   # partial block is shared
        src_dst = m.cow_for_append("a")
        assert src_dst is not None
        blk, off = m.append("a")
        assert blk == src_dst[1] and off == 2
        assert m.cow_copies == 1

    def test_idle_lru_reclaim_under_pressure(self):
        m = KVCacheManager(self.CFG, prefix_sharing=True)
        m.alloc("a", 8)
        m.register_prefix("a", list(range(8)))
        m.free("a")
        assert m.idle_blocks == 2 and m.shared_blocks == 2
        m.alloc("big", 36)                  # the whole 9-block pool
        assert m.idle_blocks == 0 and m.shared_blocks == 0
        assert not m.match_prefix(list(range(8)) + [1]).warm

    def test_can_admit_counts_only_unshared_tail(self):
        cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                            num_blocks=6, block_size=4)   # 5 usable
        m = KVCacheManager(cfg, prefix_sharing=True)
        prompt = list(range(12))            # 3 full blocks
        m.alloc("a", 12)                    # a stays LIVE: its pages
        m.register_prefix("a", prompt)      # are mapped, not idle
        # free list: 2 blocks.  A COLD identical admission (worst
        # case 16 tokens = 4 pages) cannot fit; the WARM one maps 3
        # shared pages and needs only CoW-replacement + growth = 2
        hit = m.match_prefix(prompt)
        assert len(hit.blocks) == 3 and hit.cow
        assert m.can_admit(12, 4, prefix=hit)
        assert not m.can_admit(12, 4)       # cold: 4 > 2 free
        # reservations squeeze the warm path too
        assert not m.can_admit(12, 4, prefix=hit, reserved_blocks=1)

    def test_evict_readmit_maps_same_blocks(self):
        m = KVCacheManager(self.CFG, prefix_sharing=True)
        prompt = list(range(9))
        first = m.alloc("a", 9)
        m.register_prefix("a", prompt)
        m.free("a")
        hit = m.match_prefix(prompt)
        again = m.alloc("b", 9, shared_blocks=hit.blocks)
        assert again[:len(hit.blocks)] == list(first[:len(hit.blocks)])


class TestPrefixSharingEngine:
    def test_warm_tokens_identical_to_cold(self, tiny):
        model, params = tiny
        eng = _share_engine(model, params)
        prompts = [SYS + [i] for i in range(2)]
        _run(eng, prompts, new_tokens=4)
        cold = {q.rid: q.out_tokens for q in eng.done}
        # same trace again: every admission now warm
        for i in range(2):
            eng.submit(Request(rid=f"w{i}", prompt=SYS + [i],
                               max_new_tokens=4))
        s = eng.run()
        warm = {q.rid.replace("w", "r"): q.out_tokens
                for q in eng.done if str(q.rid).startswith("w")}
        assert warm == cold
        # lifetime counter: r1 already hit r0's registered prefix in
        # the cold run, then both readmissions hit
        assert s.warm_prefix_admissions == 3
        assert s.prefix_hit_tokens > 0
        assert s.shared_blocks_hw > 0

    def test_append_never_mutates_shared_page_device(self, tiny):
        # the read-only contract at the device level: serve a cold
        # request, snapshot its shared pages' bytes, then run a warm
        # request THROUGH DECODE over the same pages — the shared
        # bytes must be bit-identical after
        model, params = tiny
        eng = _share_engine(model, params)
        _run(eng, [SYS + [0]], new_tokens=4)
        hit = eng.manager.match_prefix(SYS + [0])
        assert hit.warm and hit.cow
        shared = list(hit.blocks[:-1])      # the CoW page may rewrite
        before = np.stack([np.asarray(a)[shared] for a in eng.cache.k])
        eng.submit(Request(rid="warm", prompt=SYS + [0],
                           max_new_tokens=6))
        s = eng.run()
        assert s.cow_copies >= 1
        after = np.stack([np.asarray(a)[shared] for a in eng.cache.k])
        np.testing.assert_array_equal(before, after)

    @pytest.mark.parametrize("kv_dtype", ["model", "int8"])
    def test_copy_cache_block_copies_every_leaf(self, kv_dtype):
        # the copy-on-write program: block src -> dst in every layer's
        # k, v (and scales), nothing else touched
        from apex_tpu.serving import copy_cache_block, init_cache
        cc = KVCacheConfig(num_layers=3, num_heads=2, head_dim=8,
                           num_blocks=6, block_size=4, kv_dtype=kv_dtype)
        rng = np.random.RandomState(0)
        cache = jax.tree.map(
            lambda a: jnp.asarray(rng.randint(-90, 90, a.shape)
                                  ).astype(a.dtype), init_cache(cc))
        out = copy_cache_block(cache, 2, 5)
        leaves, got = jax.tree.leaves(cache), jax.tree.leaves(out)
        assert len(got) == len(leaves) == (12 if cc.quantized else 6)
        for a, b in zip(leaves, got):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_array_equal(b[5], a[2])
            np.testing.assert_array_equal(b[:5], a[:5])

    def test_warm_admission_prefills_only_tail(self, tiny):
        model, params = tiny
        eng = _share_engine(model, params)
        _run(eng, [SYS + [0]], new_tokens=3)
        cold_prefill = eng.prefill_tokens
        assert cold_prefill == len(SYS) + 1
        eng.submit(Request(rid="warm", prompt=SYS + [0],
                           max_new_tokens=3))
        eng.run()
        # full-prompt warm hit: only the final token re-prefills
        assert eng.prefill_tokens == cold_prefill + 1

    def test_partial_warm_hit_block_aligned(self, tiny):
        # a shared-prefix-different-tail prompt maps only the full
        # matched blocks and prefills from the block boundary
        model, params = tiny
        eng = _share_engine(model, params, block_size=4)
        _run(eng, [SYS + [0]], new_tokens=3)
        base = eng.prefill_tokens
        other = SYS[:8] + [30, 31]          # 2 matched pages + tail
        # block-aligned partial hit: no CoW at admission (the tail
        # starts on a fresh page)
        hit = eng.manager.match_prefix(other)
        assert len(hit.blocks) == 2 and hit.tokens == 8 \
            and not hit.cow
        eng.submit(Request(rid="p", prompt=other, max_new_tokens=3))
        s = eng.run()
        assert s.warm_prefix_admissions == 1
        assert eng.prefill_tokens == base + (len(other) - 8)

    def test_sharing_admits_more_load(self, tiny):
        # can_admit counting only the tail is a capacity feature: a
        # pool too small for two cold worst cases takes the second
        # request warm
        model, params = tiny
        lad = BucketLadder(batch=(2,), pages=(4,))
        eng = _engine(model, params, ladder=lad, num_blocks=6,
                      block_size=4, prefix_share=True)   # 5 usable
        prompt = list(range(12))            # worst 12+4 = 4 pages
        eng.submit(Request(rid="a", prompt=prompt, max_new_tokens=4))
        eng.run()
        hit = eng.manager.match_prefix(prompt)
        assert hit.warm
        # cold readmission could NOT overlap a second cold copy; the
        # warm one needs only tail + growth
        assert eng.manager.can_admit(12, 4, prefix=hit)
        eng.submit(Request(rid="b", prompt=prompt, max_new_tokens=4))
        s = eng.run()                       # must not raise
        assert s.requests_done == 2

    def test_pool_exhaustion_still_raises(self, tiny):
        model, params = tiny
        lad = BucketLadder(batch=(1,), pages=(4,))
        eng = _engine(model, params, ladder=lad, num_blocks=5,
                      block_size=4, prefix_share=True)
        with pytest.raises(CachePoolExhausted):
            eng.manager.alloc("x", 20)      # 5 pages > 4 usable


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

class TestChunkedPrefill:
    def test_tokens_identical_to_whole_prompt(self, tiny, baseline):
        model, params = tiny
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2, 4), pages=(2, 4),
                                          chunks=(4,)),
                      prefill_chunk=4)
        s, tokens = _run(eng, PROMPTS)
        assert tokens == baseline
        assert s.prefill_chunks > 0

    @pytest.mark.parametrize("chunk", [2, 3, 6])
    def test_chunks_against_the_page_size(self, tiny, baseline, chunk):
        # pages hold 4 tokens: chunks of 2 put several tokens of a chunk
        # on one page, chunks of 3 cross a page edge every other time,
        # chunks of 6 cover two or three pages; the last chunk of a
        # prompt is front-padded onto the dump page.  The page write
        # must lose none of them.
        model, params = tiny
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2, 4), pages=(2, 4),
                                          chunks=(chunk,)),
                      prefill_chunk=chunk)
        s, tokens = _run(eng, PROMPTS)
        assert tokens == baseline
        assert s.prefill_chunks > 0

    def test_long_prompt_spans_ticks_while_decode_continues(self,
                                                            tiny):
        # the point of chunking: a long admission streams one chunk
        # per tick and the running request keeps gaining tokens in
        # between — admission cost can no longer monopolize a tick
        model, params = tiny
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2,), pages=(8,),
                                          chunks=(4,)),
                      num_blocks=64, prefill_chunk=4)
        short = Request(rid="short", prompt=[1, 2],
                        max_new_tokens=12)
        long_req = Request(rid="long", prompt=list(range(1, 17)),
                           max_new_tokens=3)
        eng.submit(short)
        progress = []

        def drip(step):
            if step == 1:
                eng.submit(long_req)
            progress.append((step, len(short.out_tokens),
                             "long" in eng.prefilling))

        eng.run(before_tick=drip)
        spanned = [p for p in progress if p[2]]
        assert len(spanned) >= 2            # prefill crossed ticks
        # the short request decoded during the long prefill
        gained = spanned[-1][1] - spanned[0][1]
        assert gained >= 1
        assert eng.prefill_chunks >= 4      # 16 tokens / 4-chunks

    def test_drain_while_prefilling_frees_everything(self, tiny):
        # SIGTERM mid-chunked-prefill: the half-written admission is
        # preempted like everything else — blocks freed, terminal
        # event emitted, no first token claimed
        class FakeResume:
            source = "sigterm"

            def __init__(self):
                self.calls = 0

            def termination_requested(self):
                self.calls += 1
                return self.calls > 2

        model, params = tiny
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2,), pages=(8,),
                                          chunks=(2,)),
                      num_blocks=64, prefill_chunk=2,
                      autoresume=FakeResume())
        eng.submit(Request(rid="long", prompt=list(range(1, 15)),
                           max_new_tokens=4))
        s = eng.run()
        assert s.drained and s.requests_preempted == 1
        assert not eng.prefilling and not eng.active
        assert eng.manager.free_blocks == eng.cache_cfg.usable_blocks

    def test_chunked_sanitized_one_compile_per_bucket(self):
        # the ladder contract with the chunk dimension armed: warmup
        # compiles decode buckets + chunk x page programs, and the
        # whole serve holds a post-warmup recompile budget of ZERO
        lad = BucketLadder(batch=(2, 4), pages=(2,), chunks=(4,))
        summary, eng = serve_smoke(
            4, max_new_tokens=3, ladder=lad, num_blocks=24,
            block_size=4, sanitize=True, autoresume=None,
            prefill_chunk=4, return_engine=True)
        assert summary.requests_done == 4
        assert summary.prefill_chunks > 0
        # 2 decode buckets + one (1, chunk, page) extend program; no
        # whole-prompt prefill programs when chunking replaces them
        assert len(summary.compiles) == 3, summary.compiles
        assert all(v == 1 for v in summary.compiles.values())

    def test_combined_modes_sanitized(self):
        # everything at once under sanitize(): speculation + sharing
        # + chunking, zero steady-state recompiles, identical output
        lad = BucketLadder(batch=(2, 4), pages=(2,), chunks=(4,))
        _, ref_eng = serve_smoke(
            4, max_new_tokens=4, ladder=lad, num_blocks=32,
            block_size=4, autoresume=None, return_engine=True)
        summary, eng = serve_smoke(
            4, max_new_tokens=4, ladder=lad, num_blocks=32,
            block_size=4, sanitize=True, autoresume=None,
            speculate_k=2, draft="self", prefill_chunk=4,
            prefix_share=True, return_engine=True)
        assert summary.requests_done == 4
        assert summary.spec_accept_rate == 1.0
        assert all(v == 1 for v in summary.compiles.values())
        assert eng.tokens_digest() == ref_eng.tokens_digest()


# ---------------------------------------------------------------------------
# the smoke driver surface
# ---------------------------------------------------------------------------

class TestServeSmokeFastPath:
    def test_spec_smoke_digest_matches_plain(self):
        lad = BucketLadder(batch=(2, 4), pages=(2,))
        _, plain = serve_smoke(3, max_new_tokens=4, ladder=lad,
                               num_blocks=24, block_size=4,
                               autoresume=None, return_engine=True)
        s, spec = serve_smoke(3, max_new_tokens=4, ladder=lad,
                              num_blocks=24, block_size=4,
                              autoresume=None, speculate_k=2,
                              draft="self", return_engine=True)
        assert spec.tokens_digest() == plain.tokens_digest()
        assert s.spec_accept_rate == 1.0

    def test_narrow_draft_smoke(self):
        lad = BucketLadder(batch=(2, 4), pages=(2,))
        _, plain = serve_smoke(3, max_new_tokens=4, ladder=lad,
                               num_blocks=24, block_size=4,
                               autoresume=None, return_engine=True)
        s, spec = serve_smoke(3, max_new_tokens=4, ladder=lad,
                              num_blocks=24, block_size=4,
                              autoresume=None, speculate_k=2,
                              draft="narrow", return_engine=True)
        assert spec.tokens_digest() == plain.tokens_digest()
        assert s.spec_accept_rate is not None
