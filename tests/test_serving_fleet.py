"""Fleet-serving tests (ISSUE-14): tensor-parallel decode parity, the
KV export/import wire format, the disaggregated prefill→decode
handoff, router scoring + sticky warm routing, the rolling weight
swap, per-replica event stamping, and the fleet-wide trace check.

The TP anchor: a tp=2 :class:`~apex_tpu.serving.ServingEngine` (the
shard_map-wrapped decode/prefill/extend programs under
``serving_tp_plan``) must emit greedy output **token-identical** to
the single-chip engine on the same request trace — the ISSUE-14
acceptance bar, pinned here on the smoke GPT.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.monitor.events import MemorySink
from apex_tpu.serving import (BucketLadder, FleetRouter, KVCacheManager,
                              Replica, Request, RequestJournal,
                              ServingEngine, ServingModelConfig,
                              TPContext, default_cache_config,
                              extract_serving_weights,
                              gather_cache_blocks, prefix_chain_keys,
                              scatter_cache_blocks, serving_tp_plan,
                              transfer_prefix)
from apex_tpu.serving.kv_cache import KVCacheConfig, init_cache
from apex_tpu.testing.standalone_gpt import GPTModel


# ---------------------------------------------------------------------------
# shared fixtures: one smoke GPT + extracted weights per module
# ---------------------------------------------------------------------------

VOCAB, HIDDEN, HEADS, LAYERS, MAX_SEQ = 64, 32, 4, 2, 64


@pytest.fixture(scope="module")
def smoke_weights():
    model = GPTModel(
        vocab_size=VOCAB, hidden_size=HIDDEN, num_layers=LAYERS,
        num_attention_heads=HEADS, max_sequence_length=MAX_SEQ,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    params = jax.jit(model.init)(
        key, jnp.zeros((1, 8), jnp.int32))["params"]
    params2 = jax.jit(model.init)(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = ServingModelConfig.from_model(model)
    return (cfg, extract_serving_weights(params, LAYERS),
            extract_serving_weights(params2, LAYERS))


def make_engine(cfg, weights, *, prefix_share=False, tp=None,
                device=None, monitor=None, replica_id=None,
                journal=None, fault=None, num_blocks=32,
                ladder=None, warm=False):
    cache_cfg = default_cache_config(cfg, num_blocks=num_blocks,
                                     block_size=4)
    if ladder is None:
        ladder = BucketLadder(batch=(2, 4), pages=(2, 4))
    tp_ctx = None
    if tp:
        tp_ctx = TPContext(cfg, cache_cfg, tp)
    e = ServingEngine(weights, cfg, cache_cfg, ladder=ladder,
                      prefix_share=prefix_share, tp=tp_ctx,
                      device=device, monitor=monitor,
                      replica_id=replica_id, journal=journal,
                      fault=fault)
    if warm:
        e.warmup()
    return e


def make_requests(n, *, seed=3, tag="", max_new=4, min_len=1,
                  span=6):
    """Mixed-length prompts of min_len..min_len+span-1 tokens —
    sized so prompt + max_new always fits the test ladder's
    4-page x 4-token span."""
    rng = np.random.RandomState(seed)
    return [Request(rid=f"{tag}r{i}",
                    prompt=[int(t) for t in rng.randint(
                        0, VOCAB, min_len + rng.randint(span))],
                    max_new_tokens=max_new)
            for i in range(n)]


# ---------------------------------------------------------------------------
# tensor-parallel decode
# ---------------------------------------------------------------------------

class TestTensorParallel:
    def test_plan_budget_and_axes(self):
        plan = serving_tp_plan(2, num_layers=3)
        assert plan.budget() == {"psum": 6}
        ax = plan.axis("tensor")
        assert ax.size == 2 and ax.kind == "tensor"
        # weight patterns resolve against auditor-style paths
        assert plan.spec_for("in0.layers[0].qkv_k") == (None, "tensor")
        assert plan.spec_for("in0.layers[1].dense_k") == ("tensor",
                                                          None)
        assert plan.spec_for("in0.layers[0].fc2_k") == ("tensor", None)
        assert plan.spec_for("in0.wte") is None          # replicated
        assert plan.spec_for("in0.layers[0].dense_b") is None
        # the cache: one (nb, hk, bs, dk) leaf a layer, heads on axis 1;
        # 3 layers' k then v are flat outputs 0-5, the tokens the 6th
        assert plan.spec_for("in1.k[0]") == (None, "tensor")
        assert plan.spec_for("in1.v[2]") == (None, "tensor")
        assert plan.spec_for("in1.k_scale[0]") is None   # bf16 plan
        assert plan.spec_for("out0") == (None, "tensor")
        assert plan.spec_for("out5") == (None, "tensor")
        assert plan.spec_for("out6") == ()
        q8 = serving_tp_plan(2, num_layers=3, quantized=True)
        assert q8.spec_for("in1.v_scale[1]") == (None, "tensor")
        assert q8.spec_for("out11") == (None, "tensor")
        assert q8.spec_for("out12") == ()

    def test_context_validation(self, smoke_weights):
        cfg, _, _ = smoke_weights
        cc = default_cache_config(cfg, num_blocks=8, block_size=4)
        with pytest.raises(ValueError, match="tp 1 must be >= 2"):
            TPContext(cfg, cc, 1)
        with pytest.raises(ValueError, match="not divisible"):
            TPContext(cfg, cc, 3)               # 4 heads % 3
        other = default_cache_config(
            ServingModelConfig(vocab_size=VOCAB, hidden_size=64,
                               num_heads=8, num_layers=LAYERS,
                               max_seq=MAX_SEQ),
            num_blocks=8, block_size=4)
        with pytest.raises(ValueError, match="head geometry"):
            TPContext(cfg, other, 2)

    def test_tp_breaks_head_packing_rejected(self):
        # d=64 packs head PAIRS: 2 heads/shard is the floor — tp that
        # leaves one head per shard must be rejected, not mis-laid-out
        from apex_tpu.ops.flash_decode import use_decode_head_packing

        cfg = ServingModelConfig(vocab_size=VOCAB, hidden_size=256,
                                 num_heads=4, num_layers=1,
                                 max_seq=MAX_SEQ)
        cc = default_cache_config(cfg, num_blocks=8, block_size=4)
        if not use_decode_head_packing(4, 64):
            pytest.skip("head packing disabled in this environment")
        TPContext(cfg, cc, 2)                   # 2 heads/shard: fine
        with pytest.raises(ValueError, match="packing"):
            TPContext(cfg, cc, 4)               # 1 head/shard: breaks

    def test_tp_rejects_draft(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        cc = default_cache_config(cfg, num_blocks=16, block_size=4)
        tp = TPContext(cfg, cc, 2)
        with pytest.raises(ValueError, match="speculative"):
            ServingEngine(weights, cfg, cc, tp=tp, speculate_k=2,
                          draft_weights=weights, draft_cfg=cfg,
                          ladder=BucketLadder(batch=(2,), pages=(2,)))

    def test_tp_rejects_device_combo(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        cc = default_cache_config(cfg, num_blocks=16, block_size=4)
        tp = TPContext(cfg, cc, 2)
        with pytest.raises(ValueError,
                           match="at most one of tp, ep, device"):
            ServingEngine(weights, cfg, cc, tp=tp,
                          device=jax.devices()[0],
                          ladder=BucketLadder(batch=(2,), pages=(2,)))

    def test_tp_greedy_token_identical(self, smoke_weights):
        """The acceptance bar: tp=2 greedy output == single-chip,
        token for token, across mixed-length requests and bucket
        changes."""
        cfg, weights, _ = smoke_weights
        base = make_engine(cfg, weights)
        for r in make_requests(5, seed=11):
            base.submit(r)
        base.run()
        want = {q.rid: q.out_tokens for q in base.done}
        tpe = make_engine(cfg, weights, tp=2)
        for r in make_requests(5, seed=11):
            tpe.submit(r)
        s = tpe.run()
        got = {q.rid: q.out_tokens for q in tpe.done}
        assert got == want
        assert s.requests_done == 5

    def test_tp_cache_leaves_shard_on_heads(self, smoke_weights):
        # one (nb, hk, bs, dk) leaf a layer, each split on its head
        # axis, before a step and after (the steps' out_shardings)
        cfg, weights, _ = smoke_weights
        e = make_engine(cfg, weights, tp=2)
        nb, hk, bs, dk = e.cache_cfg.kv_shape

        def held():
            assert len(e.cache.k) == len(e.cache.v) == LAYERS
            for leaf in e.cache.k + e.cache.v:
                assert leaf.shape == (nb, hk, bs, dk)
                assert tuple(leaf.sharding.spec) == (None, "tensor")
                assert {s.data.shape for s in
                        leaf.addressable_shards} == {(nb, hk // 2, bs, dk)}

        held()
        for r in make_requests(3, seed=4):
            e.submit(r)
        e.run()
        held()

    def test_tp_swap_keeps_ladder(self, smoke_weights):
        cfg, weights, weights2 = smoke_weights
        e = make_engine(cfg, weights, tp=2,
                        ladder=BucketLadder(batch=(2,), pages=(2,)),
                        warm=True)
        for r in make_requests(2, seed=5, max_new=2):
            e.submit(r)
        s1 = e.run()
        e.swap_weights(weights2)
        for r in make_requests(2, seed=5, max_new=2):
            e.submit(r)
        s2 = e.run()
        assert s2.compiles == s1.compiles       # zero new compiles


# ---------------------------------------------------------------------------
# KV export/import (the disaggregation wire format)
# ---------------------------------------------------------------------------

class TestKVTransfer:
    @pytest.mark.parametrize("kv_dtype", ["model", "int8"])
    def test_gather_scatter_roundtrip_bitwise(self, kv_dtype):
        cc = KVCacheConfig(num_layers=2, num_heads=2, head_dim=8,
                           num_blocks=8, block_size=4,
                           kv_dtype=kv_dtype)
        src = init_cache(cc)
        keys = jax.random.split(jax.random.PRNGKey(0), cc.num_layers)
        fill = tuple(jax.random.normal(key, cc.kv_shape, jnp.float32)
                     .astype(cc.storage_dtype) for key in keys)
        src = src._replace(k=fill, v=fill if kv_dtype == "int8"
                           else tuple(f * 2 for f in fill))
        if cc.quantized:
            sc = tuple(jax.random.uniform(key, cc.scale_shape,
                                          jnp.float32) for key in keys)
            src = src._replace(k_scale=sc,
                               v_scale=tuple(x * 0.5 for x in sc))
        blocks = jnp.asarray([3, 1, 5], jnp.int32)
        k, v, ks, vs = gather_cache_blocks(src, blocks)
        # the wire format stacks the layers' page spans
        assert k.shape == (2, 3) + cc.kv_shape[1:]
        dst = scatter_cache_blocks(init_cache(cc), k, v, ks, vs,
                                   jnp.asarray([2, 4, 6], jnp.int32))
        for i in range(cc.num_layers):
            np.testing.assert_array_equal(
                np.asarray(dst.k[i][2]), np.asarray(src.k[i][3]))
            np.testing.assert_array_equal(
                np.asarray(dst.v[i][6]), np.asarray(src.v[i][5]))
            # pages the import did not name stay as they were
            assert not np.asarray(dst.k[i])[[1, 3, 5]].any()
            if cc.quantized:
                assert ks.shape == (2, 3) + cc.scale_shape[1:]
                np.testing.assert_array_equal(
                    np.asarray(dst.k_scale[i][4]),
                    np.asarray(src.k_scale[i][1]))

    def test_register_external_parks_idle_and_admits_warm(self):
        cc = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                           num_blocks=8, block_size=4)
        mgr = KVCacheManager(cc, prefix_sharing=True)
        prompt = list(range(10))                # 2 full + 1 partial
        blocks = mgr.register_external(prompt, 3)
        assert len(blocks) == 3
        assert mgr.idle_blocks == 3 and mgr.free_blocks == 4
        # second import of the same prompt: already resident
        assert mgr.register_external(prompt, 3) is None
        m = mgr.match_prefix(prompt)
        assert m.warm and m.tokens == len(prompt) - 1 and m.cow
        assert mgr.resident_prefix(prompt) == blocks

    def test_register_external_page_mismatch(self):
        cc = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                           num_blocks=8, block_size=4)
        mgr = KVCacheManager(cc, prefix_sharing=True)
        with pytest.raises(ValueError, match="block_size mismatch"):
            mgr.register_external(list(range(10)), 2)

    def test_register_external_needs_sharing(self):
        cc = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                           num_blocks=8, block_size=4)
        with pytest.raises(ValueError, match="prefix_sharing"):
            KVCacheManager(cc).register_external([1, 2], 1)

    def test_transfer_geometry_mismatch(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        a = make_engine(cfg, weights, prefix_share=True)
        b = make_engine(cfg, weights, prefix_share=True,
                        num_blocks=32)
        b.cache_cfg = default_cache_config(cfg, num_blocks=32,
                                           block_size=8)
        with pytest.raises(ValueError, match="incompatible"):
            transfer_prefix(a, b, [1, 2, 3])

    def test_disaggregated_handoff_warm_and_identical(
            self, smoke_weights):
        """The tentpole-3 proof: prefill on engine A, KV shipped to
        engine B, B's admission lands warm (prefix_hit_tokens > 0)
        and B's output is token-identical to a colocated serve."""
        cfg, weights, _ = smoke_weights
        reqs = make_requests(3, seed=9, tag="d", min_len=5)
        solo = make_engine(cfg, weights, prefix_share=True)
        for r in make_requests(3, seed=9, tag="d", min_len=5):
            solo.submit(r)
        solo.run()
        want = {q.rid: q.out_tokens for q in solo.done}

        pf = make_engine(cfg, weights, prefix_share=True)
        dec = make_engine(cfg, weights, prefix_share=True)
        for r in reqs:
            probe = Request(rid=f"pf:{r.rid}", prompt=list(r.prompt),
                            max_new_tokens=1)
            pf.submit(probe)
        pf.run()
        for r in reqs:
            shipped = transfer_prefix(pf, dec, r.prompt)
            assert shipped is not None and shipped > 0
            dec.submit(r)
        s = dec.run()
        got = {q.rid: q.out_tokens for q in dec.done}
        assert got == want
        assert s.warm_prefix_admissions == 3
        assert s.prefix_hit_tokens > 0

    @pytest.mark.parametrize("src_tp,dst_tp", [(None, 2), (2, None),
                                               (2, 2)])
    def test_transfer_across_tp_layouts(self, smoke_weights, src_tp,
                                        dst_tp):
        # the wire format is the layers' page spans stacked, whatever
        # the two pools' shardings: a prompt prefilled on one layout
        # decodes warm on the other, token for token
        cfg, weights, _ = smoke_weights
        prompt = make_requests(1, seed=21, min_len=7)[0].prompt
        solo = make_engine(cfg, weights, prefix_share=True)
        solo.submit(Request(rid="x", prompt=list(prompt),
                            max_new_tokens=4))
        solo.run()
        pf = make_engine(cfg, weights, prefix_share=True, tp=src_tp)
        dec = make_engine(cfg, weights, prefix_share=True, tp=dst_tp)
        pf.submit(Request(rid="pf", prompt=list(prompt),
                          max_new_tokens=1))
        pf.run()
        n = transfer_prefix(pf, dec, prompt)
        assert n == dec.cache_cfg.blocks_for(len(prompt))
        src_blocks = pf.manager.resident_prefix(prompt)
        dst_blocks = dec.manager.resident_prefix(prompt)
        for a, b in zip(pf.cache.k + pf.cache.v,
                        dec.cache.k + dec.cache.v):
            np.testing.assert_array_equal(
                np.asarray(a)[src_blocks], np.asarray(b)[dst_blocks])
        dec.submit(Request(rid="x", prompt=list(prompt),
                           max_new_tokens=4))
        s = dec.run()
        assert s.warm_prefix_admissions == 1
        assert dec.done[0].out_tokens == solo.done[0].out_tokens

    @pytest.mark.parametrize("kv_dtype", ["model", "int8"])
    def test_host_payload_roundtrip(self, smoke_weights, kv_dtype):
        # the socket handoff: export to host arrays in the wire shape
        # (L, n, hk, bs, dk), import on another replica, decode warm
        from apex_tpu.serving.fleet import (export_prefix_payload,
                                            import_prefix_payload)
        cfg, weights, _ = smoke_weights
        cc = default_cache_config(cfg, num_blocks=32, block_size=4,
                                  kv_dtype=kv_dtype)

        def engine():
            return ServingEngine(
                weights, cfg, cc, prefix_share=True,
                ladder=BucketLadder(batch=(2, 4), pages=(2, 4)))

        prompt = make_requests(1, seed=23, min_len=6)[0].prompt
        solo, pf, dec = engine(), engine(), engine()
        for e, new in ((solo, 4), (pf, 1)):
            e.submit(Request(rid="x", prompt=list(prompt),
                             max_new_tokens=new))
            e.run()
        n, arrays = export_prefix_payload(pf, prompt)
        pn = pf.ladder.pick_pages(n)
        assert arrays["k"].shape == (LAYERS, pn) + cc.kv_shape[1:]
        assert arrays["v"].dtype == np.dtype(cc.storage_dtype)
        assert ("ks" in arrays) == cc.quantized
        if cc.quantized:
            assert arrays["vs"].shape == (LAYERS, pn) + cc.scale_shape[1:]
        assert import_prefix_payload(dec, prompt, n, arrays) == n
        assert import_prefix_payload(dec, prompt, n, arrays) == 0
        dec.submit(Request(rid="x", prompt=list(prompt),
                           max_new_tokens=4))
        s = dec.run()
        assert s.warm_prefix_admissions == 1
        assert dec.done[0].out_tokens == solo.done[0].out_tokens

    def test_transfer_unresident_returns_none(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        a = make_engine(cfg, weights, prefix_share=True)
        b = make_engine(cfg, weights, prefix_share=True)
        assert transfer_prefix(a, b, [1, 2, 3, 4]) is None


# ---------------------------------------------------------------------------
# router scoring + snapshots
# ---------------------------------------------------------------------------

class TestRouter:
    def test_snapshot_fields(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        e = make_engine(cfg, weights, prefix_share=True,
                        replica_id="rX")
        snap = e.router_snapshot()
        for key in ("replica", "free_blocks", "available_blocks",
                    "reserved_blocks", "queue_depth", "active",
                    "prefilling", "shed_engaged", "warm_prefix_keys",
                    "gauges"):
            assert key in snap, key
        assert snap["replica"] == "rX"
        assert snap["warm_prefix_keys"] == frozenset()
        # serve one request: its prompt pages register, keys appear
        for r in make_requests(1, seed=2, min_len=6):
            e.submit(r)
        e.run()
        assert len(e.router_snapshot()["warm_prefix_keys"]) > 0

    def test_validation(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        e1 = make_engine(cfg, weights)
        with pytest.raises(ValueError, match="at least one"):
            FleetRouter([])
        with pytest.raises(ValueError, match="duplicate"):
            FleetRouter([Replica("a", e1),
                         Replica("a", make_engine(cfg, weights))])
        with pytest.raises(ValueError, match="serve-role"):
            FleetRouter([Replica("p", make_engine(
                cfg, weights, prefix_share=True), role="prefill")])
        with pytest.raises(ValueError, match="role"):
            Replica("x", e1, role="frontend")
        with pytest.raises(ValueError, match="prefix_share"):
            FleetRouter([Replica("s", make_engine(cfg, weights)),
                         Replica("p", make_engine(cfg, weights),
                                 role="prefill")])

    def test_round_robin_cycles(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        router = FleetRouter(
            [Replica("a", make_engine(cfg, weights)),
             Replica("b", make_engine(cfg, weights))],
            policy="round_robin")
        picks = [router.route(r).replica_id
                 for r in make_requests(4, seed=1)]
        assert picks == ["a", "b", "a", "b"]

    def test_gauges_policy_balances_backlog(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        ra = Replica("a", make_engine(cfg, weights))
        rb = Replica("b", make_engine(cfg, weights))
        router = FleetRouter([ra, rb])
        for r in make_requests(6, seed=4):
            router.submit(r)
        qa = len(ra.engine.queue)
        qb = len(rb.engine.queue)
        assert qa == 3 and qb == 3, (qa, qb)

    def test_sticky_warm_routing(self, smoke_weights):
        """A prompt resident in replica A's prefix index routes to A
        even when B has identical headroom."""
        cfg, weights, _ = smoke_weights
        ra = Replica("a", make_engine(cfg, weights,
                                      prefix_share=True))
        rb = Replica("b", make_engine(cfg, weights,
                                      prefix_share=True))
        router = FleetRouter([ra, rb])
        warm_req = make_requests(1, seed=8, min_len=9)[0]
        ra.engine.submit(Request(rid="seed", prompt=list(
            warm_req.prompt), max_new_tokens=2))
        ra.engine.run()
        assert router.route(warm_req).replica_id == "a"
        assert router.sticky_routes == 1
        # an unrelated prompt still balances away from a's backlog
        cold = Request(rid="cold", prompt=[63, 62, 61, 60],
                       max_new_tokens=2)
        assert router.route(cold).replica_id in ("a", "b")

    def test_unroutable_when_all_stopped(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        r = Replica("a", make_engine(cfg, weights))
        router = FleetRouter([r])
        r.routable = False
        with pytest.raises(RuntimeError, match="no routable"):
            router.route(make_requests(1)[0])

    def test_gauges_router_snapshot(self):
        from apex_tpu.serving import EngineGauges

        g = EngineGauges(every=4)
        g.observe(0, free_blocks=7, used_blocks=3)
        snap = g.router_snapshot()
        assert snap["free_blocks"] == 7
        assert snap["used_blocks_high_water"] == 3
        # reading the snapshot does NOT advance the cadence window
        assert g.observe(1, free_blocks=6, used_blocks=4) is None


# ---------------------------------------------------------------------------
# fleet drive loops: stepped, swap, crash replay, threads
# ---------------------------------------------------------------------------

class TestFleetServe:
    def test_stepped_completes_all(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        router = FleetRouter([
            Replica("r0", make_engine(cfg, weights)),
            Replica("r1", make_engine(cfg, weights))])
        s = router.serve(make_requests(6, seed=21))
        assert s.requests_done == 6
        assert s.lost_requests == 0
        assert s.requests_submitted == 6
        assert s.replicas == 2 and not s.threaded
        assert set(s.per_replica) == {"r0", "r1"}

    def test_rolling_swap_zero_lost_and_weights_replaced(
            self, smoke_weights):
        cfg, weights, weights2 = smoke_weights
        mk = lambda: make_engine(cfg, weights, warm=True)
        router = FleetRouter([Replica("r0", mk()),
                              Replica("r1", mk())])
        reqs = make_requests(6, seed=31, max_new=6)
        s = router.serve(reqs, swap_after=2, swap_weights=weights2)
        assert s.swaps == 2
        assert s.lost_requests == 0
        assert s.requests_done == 6
        # the swap really replaced the model: a fresh request now
        # decodes under weights2 — compare against a weights2 engine
        probe = make_requests(1, seed=77, min_len=6)[0]
        ref = make_engine(cfg, weights2)
        ref.submit(Request(rid=probe.rid, prompt=list(probe.prompt),
                           max_new_tokens=probe.max_new_tokens))
        ref.run()
        want = {q.rid: q.out_tokens for q in ref.done}
        target = router.serve([probe])
        assert target.lost_requests == 0
        got = {q.rid: q.out_tokens
               for r in router.serve_replicas
               for q in r.engine.done if q.rid == probe.rid}
        assert got == want
        # and the compiled ladder survived: no replica recompiled
        for r in router.serve_replicas:
            assert all(v == 1 for v in r.engine._compiles.values())

    def test_rolling_requant_swap_bf16_to_int8(self, smoke_weights):
        # the Q8 rollout path (ISSUE-16): a rolling swap hands each
        # replica an int8-quantized pytree; the treedef changes, so the
        # recompile is charged to the drained swap window — steady
        # state afterwards must stay zero-recompile, with zero lost
        # requests during the roll
        from apex_tpu.ops.quant_matmul import (is_quantized_weights,
                                               quantize_weights)
        cfg, weights, _ = smoke_weights
        qweights = quantize_weights(weights)
        mk = lambda: make_engine(cfg, weights, warm=True)
        router = FleetRouter([Replica("r0", mk()),
                              Replica("r1", mk())])
        reqs = make_requests(6, seed=41, max_new=6)
        s = router.serve(reqs, swap_after=2, swap_weights=qweights)
        assert s.swaps == 2
        assert s.lost_requests == 0
        assert s.requests_done == 6
        for r in router.serve_replicas:
            assert is_quantized_weights(r.engine.weights)
        # steady state after the swap: more traffic, no new compiles
        before = {r.replica_id: dict(r.engine._compiles)
                  for r in router.serve_replicas}
        more = router.serve(make_requests(4, seed=43))
        assert more.lost_requests == 0
        assert more.requests_done - s.requests_done == 4
        for r in router.serve_replicas:
            assert dict(r.engine._compiles) == before[r.replica_id]

    def test_swap_requires_idle(self, smoke_weights):
        cfg, weights, weights2 = smoke_weights
        e = make_engine(cfg, weights)
        e.submit(make_requests(1)[0])
        with pytest.raises(RuntimeError, match="busy"):
            e.swap_weights(weights2)

    def test_swap_shape_mismatch(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        e = make_engine(cfg, weights)
        bad = weights._replace(wte=jnp.zeros((VOCAB, HIDDEN * 2),
                                             jnp.float32))
        with pytest.raises(ValueError, match="swap_weights leaf"):
            e.swap_weights(bad)

    def test_crash_replay_in_fleet(self, smoke_weights, tmp_path):
        from apex_tpu.resilience import parse_fault

        cfg, weights, _ = smoke_weights
        j0 = RequestJournal(str(tmp_path / "r0.journal.jsonl"))
        router = FleetRouter([
            Replica("r0", make_engine(cfg, weights, journal=j0),
                    journal=j0, fault=parse_fault("crash@2")),
            Replica("r1", make_engine(cfg, weights))])
        s = router.serve(make_requests(8, seed=41, max_new=6))
        assert s.restarts == 1
        assert s.replayed_requests > 0
        assert s.lost_requests == 0
        assert s.requests_done == 8

    def test_unjournaled_crash_propagates(self, smoke_weights):
        from apex_tpu.resilience import parse_fault
        from apex_tpu.resilience.faults import InjectedCrash

        cfg, weights, _ = smoke_weights
        router = FleetRouter([
            Replica("r0", make_engine(cfg, weights),
                    fault=parse_fault("crash@1"))])
        with pytest.raises(InjectedCrash):
            router.serve(make_requests(2, seed=1, max_new=4))

    def test_threaded_completes_all(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        devs = jax.devices()
        router = FleetRouter([
            Replica("t0", make_engine(cfg, weights,
                                      device=devs[0])),
            Replica("t1", make_engine(cfg, weights,
                                      device=devs[1 % len(devs)]))])
        s = router.serve_threaded(make_requests(6, seed=51))
        assert s.requests_done == 6 and s.lost_requests == 0
        assert s.threaded
        # shares balanced by the planned-backlog scoring
        done = {r.replica_id: len(r.engine.done)
                for r in router.serve_replicas}
        assert done["t0"] == 3 and done["t1"] == 3, done

    def test_threaded_rejects_disagg(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        router = FleetRouter([
            Replica("s", make_engine(cfg, weights,
                                     prefix_share=True)),
            Replica("p", make_engine(cfg, weights,
                                     prefix_share=True),
                    role="prefill")])
        with pytest.raises(ValueError, match="stepped"):
            router.serve_threaded(make_requests(2))

    def test_disaggregated_stepped(self, smoke_weights):
        cfg, weights, _ = smoke_weights
        router = FleetRouter([
            Replica("d0", make_engine(cfg, weights,
                                      prefix_share=True)),
            Replica("pf0", make_engine(cfg, weights,
                                       prefix_share=True),
                    role="prefill")])
        s = router.serve(make_requests(4, seed=61, min_len=5))
        assert s.handoffs > 0
        assert s.prefix_hit_tokens > 0
        assert s.warm_prefix_admissions > 0
        assert s.lost_requests == 0 and s.requests_done == 4


# ---------------------------------------------------------------------------
# replica stamping + fleet trace aggregation
# ---------------------------------------------------------------------------

class TestFleetObservability:
    def test_replica_monitor_stamps_events(self, smoke_weights):
        from apex_tpu.monitor import StepMonitor

        cfg, weights, _ = smoke_weights
        sink = MemorySink()
        mon = StepMonitor(sink, close_sink=False)
        e = make_engine(cfg, weights, monitor=mon, replica_id="r7")
        for r in make_requests(2, seed=71):
            e.submit(r)
        e.run()
        srv = [ev for ev in sink.events if ev.kind == "serving"]
        assert srv and all(ev.attrs.get("replica") == "r7"
                           for ev in srv)
        # explicit replica attrs win over the stamp
        e.monitor.event("fleet", "probe", replica="other")
        probe = [ev for ev in sink.events if ev.name == "probe"][0]
        assert probe.attrs["replica"] == "other"

    def test_check_serve_trace_fleet(self, smoke_weights, tmp_path):
        from apex_tpu.monitor import JsonlSink, StepMonitor
        from apex_tpu.monitor.tracing import check_serve_trace

        cfg, weights, _ = smoke_weights
        paths = []
        for i in range(2):
            path = str(tmp_path / f"serve-r{i}.jsonl")
            paths.append(path)
            mon = StepMonitor(JsonlSink(path))
            e = make_engine(cfg, weights, monitor=mon,
                            replica_id=f"r{i}")
            for r in make_requests(2, seed=80 + i, tag=f"x{i}"):
                e.submit(r)
            e.run()
            mon.close()
        assert check_serve_trace(paths) == []
        # a rid living on two replicas must fail the fleet check
        dup = str(tmp_path / "dup.jsonl")
        with open(paths[0]) as f, open(dup, "w") as g:
            for line in f:
                ev = json.loads(line)
                if ev.get("attrs", {}).get("replica") == "r0":
                    ev["attrs"]["replica"] = "r9"
                g.write(json.dumps(ev) + "\n")
        failures = check_serve_trace([paths[0], dup])
        assert any("lifecycle events on 2 replicas" in f
                   for f in failures), failures

    def test_fleet_summary_digest(self, smoke_weights, tmp_path):
        from apex_tpu.monitor.summary import load_events, summarize

        cfg, weights, _ = smoke_weights
        path = str(tmp_path / "fleet.jsonl")
        from apex_tpu.monitor import JsonlSink, StepMonitor

        mon = StepMonitor(JsonlSink(path))
        router = FleetRouter(
            [Replica("r0", make_engine(cfg, weights, monitor=mon,
                                       replica_id="r0")),
             Replica("r1", make_engine(cfg, weights, monitor=mon,
                                       replica_id="r1"))],
            monitor=mon)
        router.serve(make_requests(4, seed=91))
        mon.close()
        events, malformed = load_events(path)
        digest = summarize(events, malformed)["serving"]
        reps = digest["replicas"]
        assert set(reps) == {"r0", "r1"}
        assert all(v["submitted"] == v["terminal"]
                   for v in reps.values())
        assert digest["fleet"]["routed"] == 4

    def test_fleet_flags_registered(self):
        from apex_tpu.analysis.flags import FLAGS, flag_value

        for name in ("APEX_TPU_SERVE_REPLICAS", "APEX_TPU_SERVE_TP",
                     "APEX_TPU_SERVE_DISAGGREGATE",
                     "APEX_TPU_SERVE_ROUTER"):
            assert name in FLAGS, name
        assert flag_value("APEX_TPU_SERVE_REPLICAS") == 1
        assert flag_value("APEX_TPU_SERVE_ROUTER") == "gauges"

    def test_prefix_chain_keys_shared_convention(self):
        cc = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                           num_blocks=8, block_size=4)
        mgr = KVCacheManager(cc, prefix_sharing=True)
        prompt = list(range(9))
        keys, pkey = prefix_chain_keys(prompt, 4)
        mkeys, mpkey = mgr._chain_keys(prompt)
        assert keys == mkeys and pkey == mpkey
        assert len(keys) == 2 and pkey is not None
