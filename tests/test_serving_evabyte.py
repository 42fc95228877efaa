"""EVA layers on the serving path (``rope_moe``'s dense layer with an
aligned window beside pooled chunks, over the pooled cache) against the
plain float32 reference of the configuration that uses them
(``benchmarks/reference_evabyte.py``, which shares no code with
``apex_tpu``), at tiny sizes on the CPU: **logits are compared, every
prediction head's**, through the same step functions, paged cache,
manager and kernels (interpreted here) that the engine runs.

The tiny model keeps the published structure: every layer the same EVA
block, the norm's unit offset, full-width rotary, a head three
vocabularies wide; window 32, chunk 4 = pages of 4, so a window is 8
pages and pools to 2 summary pages.  Sequences cross three or more
window boundaries and end mid-chunk.

Tolerances, each with its reason, are at their use.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from apex_tpu.ops.flash_decode import (eva_attention_reference,
                                       eva_flash_decode)
from apex_tpu.serving import (BucketLadder, KVCacheManager, Request,
                              ServingEngine, default_cache_config,
                              init_cache)
from apex_tpu.serving.kv_cache import DUMP_BLOCK
from apex_tpu.serving.model import decode_logits, prefill_logits
from benchmarks import builders_evabyte, reference_evabyte

WINDOW, CHUNK = 32, 4
TINY = dict(
    vocab_size=40, hidden_size=64, intermediate_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    rms_norm_eps=1e-5, rope_theta=100000, window_size=WINDOW,
    chunk_size=CHUNK, num_pred_heads=3, norm_add_unit_offset=True,
    init_std=0.05)
MAX_SEQ = 160
RUNGS = (16, 32)

# float32 against float32 through other code paths (the kernels in
# blocks with an online softmax, the pooled rows through pages): rounding
# alone.  The whole forward reads ~1e-6 of logits whose spread is ~0.3;
# anything structural -- a window edge or a summary visible a window
# early, a freed or foreign page, a missing mu, a head read at the wrong
# offset -- moves a logit by 1e-3 or more.
F32_TOL = 2e-5


def model(dtype=jnp.float32, seed=1, **kw):
    cfg = builders_evabyte.serving_config(TINY, max_seq=MAX_SEQ,
                                          dtype=dtype, **kw)
    return cfg, builders_evabyte.make_weights(TINY, cfg, seed)


def tokens_of(n, seed=3):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0,
                                         TINY["vocab_size"]))


def reference(weights, tokens):
    return np.asarray(reference_evabyte.logits(weights, jnp.asarray(tokens),
                                               TINY))


class Served:
    """One request through the step functions, driven as the engine
    drives them: a window a prefill chunk, then a position a decode
    step, the manager's two lists deciding every page."""

    def __init__(self, cfg, weights, num_blocks=64, batch=2, pages=16):
        self.cfg, self.w = cfg, weights
        self.ccfg = default_cache_config(cfg, num_blocks=num_blocks,
                                         kv_dtype="model")
        self.cache = init_cache(self.ccfg)
        self.mgr = KVCacheManager(self.ccfg)
        self.batch, self.pages = batch, pages
        self.capacity = (MAX_SEQ - 1) // WINDOW \
            * self.ccfg.window_summary_pages

    def prefill(self, rid, tokens):
        """The prompt a window a chunk; the logits of its last
        position."""
        mgr, bs = self.mgr, CHUNK
        for start in range(0, len(tokens), WINDOW):
            n = min(WINDOW, len(tokens) - start)
            ct = next(r for r in RUNGS if n <= r)
            if start == 0:
                mgr.alloc(rid, n)
            else:
                mgr.grow_to(rid, start + n)
            toks = np.zeros(ct, np.int32)
            toks[:n] = tokens[start:start + n]
            blocks = np.full(ct // bs, DUMP_BLOCK, np.int32)
            blocks[:len(mgr.blocks(rid))] = mgr.blocks(rid)
            summaries = mgr.summary_blocks(rid)
            closed = start // WINDOW * self.ccfg.window_summary_pages
            table = np.full(self.capacity, DUMP_BLOCK, np.int32)
            table[:closed] = summaries[:closed]
            pool = np.full(ct // bs ** 2, DUMP_BLOCK, np.int32)
            pool[:len(summaries) - closed] = summaries[closed:]
            self.cache, last = prefill_logits(
                self.w, self.cfg, self.ccfg, self.cache, jnp.asarray(toks),
                jnp.int32(n), jnp.asarray(blocks), jnp.int32(start),
                jnp.asarray(table), jnp.asarray(pool))
            mgr.close_window(rid)
        return np.asarray(last)

    def decode(self, feeds):
        """One step: ``feeds`` {rid: token}; {rid: logits}."""
        mgr, bb, pb = self.mgr, self.batch, self.pages
        z = np.zeros(bb, np.int32)
        tokens, positions, lens, wo, po = (z.copy() for _ in range(5))
        wb, pk = (np.full(bb, DUMP_BLOCK, np.int32) for _ in range(2))
        bt = np.full((bb, pb), DUMP_BLOCK, np.int32)
        for i, (rid, token) in enumerate(feeds.items()):
            wb[i], wo[i] = mgr.append(rid)
            pk[i], po[i] = mgr.pool_slot(rid)
            tokens[i], lens[i] = token, mgr.seq_len(rid)
            positions[i] = lens[i] - 1
            bt[i] = mgr.block_table(rid, pb)
        self.cache, logits, _ = decode_logits(
            self.w, self.cfg, self.ccfg, self.cache, *map(jnp.asarray, (
                tokens, positions, bt, lens, wb, wo, pk, po)))
        for rid in feeds:
            mgr.close_window(rid)
        return {rid: np.asarray(logits[i]) for i, rid in enumerate(feeds)}


class TestAgainstTheReference:
    @pytest.mark.parametrize("decode_attention, prefill_flash", [
        ("kernel", True), ("reference", False)])
    def test_chunked_prefill_then_decode_over_window_boundaries(
            self, decode_attention, prefill_flash):
        """A prompt of 2.3 windows (two full chunks and a 10-position
        rung-16 tail that ends mid-chunk), then 61 decode steps over two
        more window boundaries to 4.2 windows, beside a second row that
        starts inside its first window and crosses one boundary: every
        step's logits, all three prediction heads', are the reference's
        full forward at that position."""
        cfg, w = model(decode_attention=decode_attention,
                       prefill_flash=prefill_flash)
        served = Served(cfg, w)
        toks, other = tokens_of(135), tokens_of(50, seed=5)
        want, want_other = reference(w, toks), reference(w, other)
        n0, m0 = 74, 21
        got = served.prefill("a", toks[:n0])
        assert np.abs(got - want[n0 - 1]).max() < F32_TOL
        got = served.prefill("b", other[:m0])
        assert np.abs(got - want_other[m0 - 1]).max() < F32_TOL
        worst = 0.0
        for step in range(len(toks) - n0):
            feeds = {"a": toks[n0 + step]}
            if m0 + step < len(other):
                feeds["b"] = other[m0 + step]
            out = served.decode(feeds)
            worst = max(worst, np.abs(out["a"] - want[n0 + step]).max())
            if "b" in out:
                worst = max(worst, np.abs(
                    out["b"] - want_other[m0 + step]).max())
        assert worst < F32_TOL
        assert want.shape[1] == 3 * TINY["vocab_size"]
        # the windows closed on the way gave their pages back
        assert served.mgr.held_blocks("a") \
            == -(-(135 % WINDOW) // CHUNK) + -(-(135 // CHUNK) // CHUNK)

    def test_a_prompt_of_whole_windows_starts_decode_on_an_empty_one(self):
        cfg, w = model()
        served = Served(cfg, w)
        toks = tokens_of(70)
        want = reference(w, toks)
        got = served.prefill("a", toks[:64])
        assert served.mgr.blocks("a") == []
        assert np.abs(got - want[63]).max() < F32_TOL
        for at in range(64, 70):
            out = served.decode({"a": toks[at]})["a"]
            assert np.abs(out - want[at]).max() < F32_TOL

    def test_bf16_serves_inside_a_tolerance_a_float8_forward_fails(self):
        """The bf16 engine path (weights, activations and cache in
        bf16) against the float32 reference ON THE SAME bf16 weights
        reads a few 1e-3 of logits whose spread is ~0.3; the same path
        on weights rounded once more to float8_e4m3 reads ten times
        that.  The limit stands between."""
        cfg, w = model(dtype=jnp.bfloat16)
        toks = tokens_of(100)
        want = reference(w, toks)

        def worst(weights):
            served = Served(cfg, weights)
            err = np.abs(served.prefill("a", toks[:70]) - want[69]).max()
            for at in range(70, 100):
                out = served.decode({"a": toks[at]})["a"]
                err = max(err, np.abs(out - want[at]).max())
            return err

        low = jax.tree.map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            if x.dtype == jnp.bfloat16 else x, w)
        bf16, float8 = worst(w), worst(low)
        assert bf16 < 0.03 < float8, (bf16, float8)


class TestTheEngine:
    def engine(self, cfg, w, **kw):
        ccfg = default_cache_config(cfg, num_blocks=80, kv_dtype="model")
        return ServingEngine(
            w, cfg, ccfg, ladder=BucketLadder(
                batch=(2, 4), pages=(8, 16), chunks=RUNGS),
            speculate_k=0, prefill_chunk=WINDOW, prefix_share=False,
            slo=None, **kw)

    def test_emitted_tokens_are_the_references_greedy_head_0(self):
        """Three requests through ``ServingEngine`` (admission, a window
        a prefill chunk, decode over window boundaries, pages freed
        behind the window): every emitted byte is the arg-max of the
        reference's head 0 on the request's own sequence, or within
        float32 rounding of it."""
        cfg, w = model()
        eng = self.engine(cfg, w)
        prompts = [tokens_of(75, 7), tokens_of(9, 8), tokens_of(40, 9)]
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=f"r{i}", prompt=[int(t) for t in p],
                               max_new_tokens=45))
        eng.run()
        assert len(eng.done) == 3
        v = TINY["vocab_size"]
        for req in eng.done:
            assert req.terminal == "finished"
            seq = np.asarray(req.prompt + req.out_tokens)
            lg = reference(w, seq)[len(req.prompt) - 1:-1, :v]
            took = lg[np.arange(len(req.out_tokens)), req.out_tokens]
            assert (lg.max(-1) - took).max() < F32_TOL
        # nothing is left held, and windows were closed on the way
        assert eng.manager.free_blocks == eng.cache_cfg.usable_blocks

    def test_the_tick_uploads_two_arrays_not_eight(self):
        """Every numpy argument of the tick is an upload the device waits
        for (~0.12 ms each on the chip, ``PERF.md`` section 6): the
        pooled cache's tick takes its seven vectors a row as one array
        beside the block table."""
        cfg, w = model()
        eng = self.engine(cfg, w)
        args = eng._decode_args(4, 16)
        assert [a.shape for a in args[2:]] == [(7, 4), (4, 16)]
        text = eng._jit_decode().lower(*args).as_text()
        params = text[text.index("@main("):].split(") ->", 1)[0]
        assert params.count("xi32>") == 2, params

    def test_the_engine_counts_what_eva_reads_while_a_profile_records(self):
        from apex_tpu.monitor import tracing

        cfg, w = model()
        eng = self.engine(cfg, w)
        eng.submit(Request(rid="a", prompt=[1] * 70, max_new_tokens=30))
        eng.submit(Request(rid="b", prompt=[2] * 93, max_new_tokens=8))
        tracing.set_tracer(tracing.SpanTracer())
        try:
            eng.run()
        finally:
            tracing.set_tracer(None)
        sums = eng.tick_sums
        # a: chunks of 32, 32, 6; b: 32, 32, 29; the pooled rows before
        # each chunk are start / 4
        assert sums["eva_chunks"] == 6
        assert sums["eva_chunk_tokens"] == 70 + 93
        assert sums["eva_chunk_summary_rows"] == 2 * (0 + 8 + 16)
        assert sums["eva_chunk_pairs"] == sum(
            n * (n + 1) // 2 + n * rows for n, rows in
            [(32, 0), (32, 8), (6, 16), (32, 0), (32, 8), (29, 16)])
        # windows closed: two a prompt by their chunks, and by decode a
        # at 96 and b at 96
        assert sums["eva_windows_closed"] == 6
        assert sums["eva_pages_freed"] == 6 * 8
        assert 0 < sums["eva_pages_live"] <= sums["eva_pages_slots"]
        assert sums["eva_window_rows"] > 0 and sums["eva_summary_rows"] > 0
        assert 0 < eng.manager.used_blocks_hw \
            <= 2 * eng.cache_cfg.blocks_for(101)

    @pytest.mark.parametrize("kw, message", [
        (dict(prefill_chunk=0), "a window a chunk"),
        (dict(prefill_chunk=16), "a window a chunk"),
        (dict(speculate_k=1, draft_weights=object(), draft_cfg=object()),
         "speculative decoding"),
        (dict(prefix_share=True), "prefix sharing"),
    ])
    def test_what_the_pooled_cache_does_not_serve_is_refused_by_name(
            self, kw, message):
        cfg, w = model()
        ccfg = default_cache_config(cfg, num_blocks=40, kv_dtype="model")
        args = dict(ladder=BucketLadder(batch=(2,), pages=(16,),
                                        chunks=RUNGS),
                    speculate_k=0, prefill_chunk=WINDOW,
                    prefix_share=False, slo=None)
        with pytest.raises(ValueError, match=message):
            ServingEngine(w, cfg, ccfg, **{**args, **kw})

    def test_int8_tp_and_ep_refuse_the_kind_by_name(self):
        from apex_tpu.serving import KVCacheConfig
        from apex_tpu.serving.ep import EPContext
        from apex_tpu.serving.tp import TPContext

        cfg, w = model()
        with pytest.raises(ValueError, match="pooled cache.*int8"):
            default_cache_config(cfg, num_blocks=40, kv_dtype="int8")
        with pytest.raises(ValueError, match="multiple of block_size"):
            KVCacheConfig(num_layers=1, num_heads=4, head_dim=16,
                          num_blocks=9, block_size=4, window=24)
        ccfg = default_cache_config(cfg, num_blocks=40, kv_dtype="model")
        from apex_tpu.serving import ServingModelConfig
        dense = ServingModelConfig(vocab_size=40, hidden_size=64,
                                   num_heads=4, num_layers=1, max_seq=64)
        with pytest.raises(ValueError, match="pooled cache.*tensor"):
            TPContext(dense, ccfg, 2)
        with pytest.raises(ValueError, match="pooled cache.*expert"):
            EPContext(dense, ccfg, 2)
        with pytest.raises(ValueError, match="EVA layers"):
            ServingEngine(w, dense, ccfg, speculate_k=0,
                          prefill_chunk=0, prefix_share=False, slo=None,
                          ladder=BucketLadder(batch=(2,), pages=(8,)))


class TestTheKernelsAgainstTheirTwins:
    def test_decode_two_segments_one_softmax(self):
        """Rows with 0, 1 and 2 closed windows of pooled rows before a
        window filled to 1, 13 and 32 rows (its last page part full,
        full), an inactive row, block ids interleaved: the interpreted
        kernel against the dense twin, and the twin against a direct
        softmax over the two segments' rows."""
        key = jax.random.PRNGKey(0)
        nb, h, bs, d = 40, 4, 4, 16
        kc, vc, q = (jax.random.normal(k, s, jnp.float32)
                     for k, s in zip(jax.random.split(key, 3), (
                         (nb, h, bs, d), (nb, h, bs, d), (4, h, d))))
        summary = np.array([0, 8, 16, 0], np.int32)
        window = np.array([1, 13, 32, 0], np.int32)
        ids = iter(np.random.default_rng(0).permutation(np.arange(1, nb)))
        bt = np.zeros((4, 12), np.int32)
        for b in range(4):
            n = summary[b] // bs + -(-window[b] // bs)
            bt[b, :n] = [next(ids) for _ in range(n)]
        args = (q, kc, vc, jnp.asarray(bt), jnp.asarray(summary),
                jnp.asarray(window))
        got, twin = eva_flash_decode(*args), eva_attention_reference(*args)
        assert np.abs(np.asarray(got) - np.asarray(twin)).max() < 2e-6
        assert np.all(np.asarray(got)[3] == 0)
        for b in range(3):
            rows = summary[b] + window[b]
            pages = bt[b, :-(-rows // bs)]
            k = np.asarray(kc)[pages].transpose(1, 0, 2, 3) \
                .reshape(h, -1, d)[:, :rows]
            v = np.asarray(vc)[pages].transpose(1, 0, 2, 3) \
                .reshape(h, -1, d)[:, :rows]
            s = np.einsum("hd,hkd->hk", np.asarray(q)[b], k) / 4.0
            p = np.exp(s - s.max(-1, keepdims=True))
            want = np.einsum("hk,hkd->hd", p / p.sum(-1, keepdims=True), v)
            assert np.abs(np.asarray(twin)[b] - want).max() < 2e-6

    @pytest.mark.parametrize("sq, prefix, there", [
        (32, 16, 8), (32, 16, 0), (16, 16, 16), (160, 128, 40)])
    def test_prefill_forward_with_a_pooled_prefix(self, sq, prefix, there):
        """``prefix`` rows before the causal square, the first ``there``
        of them there: the interpreted forward (one block, and at 160 +
        128 keys the blocked walk) against the dense twin."""
        key = jax.random.PRNGKey(1)
        q, k, v = (jax.random.normal(kk, (1, 4, n, 16), jnp.float32)
                   for kk, n in zip(jax.random.split(key, 3),
                                    (sq, prefix + sq, prefix + sq)))
        at = jnp.arange(prefix + sq)
        mask = ((at < there) | (at >= prefix))[None]
        got = flash_attention(q, k, v, causal=True, prefix=prefix,
                              kv_mask=mask, block_q=64, block_k=128)
        want = mha_reference(q, k, v, causal=True, prefix=prefix,
                             kv_mask=mask)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
        # the twin itself: row 0 sees the prefix rows that are there
        # and its own key, nothing else
        s = np.einsum("d,kd->k", np.asarray(q)[0, 0, 0],
                      np.asarray(k)[0, 0]) / 4.0
        seen = np.r_[np.arange(there), prefix]
        p = np.exp(s[seen] - s[seen].max())
        row = (p / p.sum()) @ np.asarray(v)[0, 0][seen]
        assert np.abs(np.asarray(want)[0, 0, 0] - row).max() < 2e-6


class TestTheManager:
    """The pooled kind's bookkeeping: two lists a request, window pages
    back at the boundary, a reservation that holds."""

    BS, W = 4, 32

    def manager(self, num_blocks=200):
        from apex_tpu.serving import KVCacheConfig

        cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=16,
                            num_blocks=num_blocks, block_size=self.BS,
                            window=self.W)
        return cfg, KVCacheManager(cfg)

    def expected(self, t):
        """(window pages, summary pages) of a request at ``t`` positions,
        its closed window given back."""
        bs, w = self.BS, self.W
        return -(-(t % w) // bs), -(-(t // bs) // bs)

    @pytest.mark.parametrize("first", [1, 13, 32])
    def test_the_two_lists_follow_the_position_at_every_length(self, first):
        cfg, mgr = self.manager()
        usable = cfg.usable_blocks
        mgr.alloc("a", first)
        mgr.close_window("a")
        for t in range(first, 5 * self.W + 7):
            assert mgr.seq_len("a") == t
            window, summaries = self.expected(t)
            assert len(mgr.blocks("a")) == window
            assert len(mgr.summary_blocks("a")) == summaries
            assert mgr.held_blocks("a") == window + summaries \
                <= cfg.blocks_for(t)
            assert mgr.free_blocks + mgr.held_blocks("a") == usable
            # the table: the closed windows' summary pages, then the
            # window's; what a step at this length would read
            closed = max(t - 1, 0) // self.W
            assert mgr.num_pages("a") == 2 * closed + window \
                <= cfg.table_pages(max(t, 1))
            block, off = mgr.append("a")
            assert off == t % self.BS and block == mgr.blocks("a")[-1]
            slot = mgr.pool_slot("a")
            if (t + 1) % self.BS:
                assert slot == (DUMP_BLOCK, 0)
            else:
                chunk = t // self.BS
                assert slot == (mgr.summary_blocks("a")[chunk // self.BS],
                                chunk % self.BS)
            if (t + 1) % self.W == 0:
                # all its pages until the window closes, then none
                assert len(mgr.blocks("a")) == self.W // self.BS
                held = mgr.blocks("a")
                assert mgr.close_window("a") == held
                assert mgr.blocks("a") == []
            else:
                assert mgr.close_window("a") == []
        mgr.free("a")
        assert mgr.free_blocks == usable and not mgr.requests()

    def test_growing_past_an_open_window_is_refused(self):
        _, mgr = self.manager()
        mgr.alloc("a", 32)
        with pytest.raises(RuntimeError, match="one open window"):
            mgr.grow_to("a", 40)          # the closed window still held
        mgr.close_window("a")
        with pytest.raises(RuntimeError, match="one open window"):
            mgr.grow_to("a", 70)          # two windows at once
        mgr.grow_to("a", 64)
        with pytest.raises(ValueError, match="one\\s+window"):
            mgr.alloc("b", 33)
        with pytest.raises(ValueError, match="no rollback"):
            mgr.truncate("a", 60)

    def test_reservations_never_let_a_run_exhaust_the_pool(self):
        """A seeded run of admissions, chunked growth, appends and
        finishes on a pool three worst cases deep: admission by
        ``can_admit`` net of what the pool owes the requests in flight
        (the engine's arithmetic) never lets a claim find the pool
        empty, and no block is lost."""
        cfg, mgr = self.manager(num_blocks=1 + 3 * 20)
        rng = np.random.default_rng(11)
        usable = cfg.usable_blocks
        live, n, admitted, hw = {}, 0, 0, 0

        def owed():
            return sum(max(0, cfg.blocks_for(goal) - mgr.held_blocks(rid))
                       for rid, (_, goal) in live.items())

        for _ in range(4000):
            prompt, new = int(rng.integers(1, 100)), int(rng.integers(1, 60))
            if len(live) < 6 and mgr.can_admit(prompt, new,
                                               reserved_blocks=owed()):
                rid, n, admitted = f"r{n}", n + 1, admitted + 1
                mgr.alloc(rid, min(prompt, self.W))
                mgr.close_window(rid)       # (once its chunk is written)
                live[rid] = (prompt, prompt + new)
            for rid, (prompt, goal) in list(live.items()):
                t = mgr.seq_len(rid)
                if t < prompt:              # the next prefill chunk
                    mgr.grow_to(rid, min(prompt, t - t % self.W + self.W))
                elif t < goal:
                    mgr.append(rid)
                else:
                    mgr.free(rid)
                    del live[rid]
                    continue
                assert mgr.held_blocks(rid) <= cfg.blocks_for(goal)
                mgr.close_window(rid)
            held = sum(mgr.held_blocks(rid) for rid in live)
            assert mgr.free_blocks + held == usable
            hw = max(hw, held)
        assert admitted > 100 and hw > usable // 2
