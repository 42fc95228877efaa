"""Persistent packed pipeline: math parity, overflow branch, packing,
checkpoint integrity.

The parity contract (ISSUE-4 acceptance): the pipeline's fp32 state —
masters, m/v/momentum — must be BITWISE equal to the staged
(per-stage) path for every tested config.  Elementwise update math is
identical expression-for-expression, so under jit both paths compile
the same IEEE op sequence; the one place reduction ORDER enters is the
clip factor's global norm (packed (rows,128) reduce vs the staged
per-group reduce), so clip-on configs are compared bitwise against a
staged reference that consumes the pipeline's own norm (the combined
``inv*clip`` factor applied exactly as the update sweep applies it)
and within 1e-6 of the fully-independent staged amp path.  An optax
(unscale→clip→optax.adamw) cross-check pins the math to the ecosystem
reference within fp32 roundoff (optax's integer-exponent ``decay**t``
differs from our float-exponent bias correction in the last ulp, so
that comparison is tight-tolerance, not bitwise).
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu import amp
from apex_tpu.ops import fused_pipeline as fp
from apex_tpu.ops import multi_tensor as mt
from apex_tpu.optimizers import fused_adam, fused_lamb, fused_sgd
from apex_tpu.optimizers.fused_adam import _grad_clip_factor


def tree_bitwise(a, b, msg="", max_ulp=0):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        if max_ulp:
            np.testing.assert_array_max_ulp(x, y, maxulp=max_ulp)
        else:
            np.testing.assert_array_equal(x, y, err_msg=msg)


# L2-mode weight decay over a low-precision model, ``g + wd * p`` ahead
# of the moments: the packed pipeline and the staged path write the same
# arithmetic, and jax 0.9.0's CPU compiler contracts that multiply-add
# into one rounding in the staged graph and not in the packed one.  An
# emulation in numpy with the one fused multiply-add reproduces the
# staged masters bit for bit and the packed ones without it (SGD, all
# 145 elements, three steps; Adam's L2 mode writes the same expression
# and shows the same gap), so the fp32 masters of those configurations
# are held to one ulp; the model copy and every other configuration
# stay bitwise.
L2_CONTRACTION_ULP = 1


def make_params(dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {
        "dense": {"kernel": jax.random.normal(ks[0], (9, 11),
                                              jnp.float32),
                  "bias": jax.random.normal(ks[1], (11,), jnp.float32)},
        "out": jax.random.normal(ks[2], (7, 5), jnp.float32),
    }


def grads_for(model, i, scale):
    return jax.tree_util.tree_map(
        lambda x: ((x.astype(jnp.float32) * 0.03 + 0.01 * (i + 1))
                   * scale).astype(x.dtype), model)


def _policy(dtype, scale):
    if dtype == jnp.float32:
        # master-weight pipeline over an uncast (fp32) model: grads
        # arrive fp32, masters fp32 — the pure-precision corner
        return amp.get_policy("O5", loss_scale=scale,
                              cast_model_type=jnp.float32)
    return amp.get_policy("O2" if dtype == jnp.float16 else "O5",
                          loss_scale=scale,
                          cast_model_type=dtype)


def run_amp(make_tx, policy, params, pipeline, steps=3, use_pallas=None):
    opt = amp.AmpOptimizer(make_tx(), policy, check_finite=True,
                           pipeline=pipeline)
    state = opt.init(params)
    model = jax.tree_util.tree_map(
        lambda x: x.astype(policy.param_dtype), params)
    step = jax.jit(opt.apply_gradients)
    info = None
    for i in range(steps):
        g = grads_for(model, i, policy.effective_loss_scale)
        model, state, info = step(g, state, model)
    return model, state, info


def unpacked_masters(state, params):
    return state.master_params.to_model(
        jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params))


def unpacked_state_bufs(bufs, metas):
    return mt.unpack_groups(list(bufs), list(metas))


# ---------------------------------------------------------------------------
# Packing primitives
# ---------------------------------------------------------------------------

class TestPacking:
    def test_pack_grads_matches_concat_pack(self):
        params = make_params()
        metas = fp.pipeline_metas(params)
        a = fp.pack_grads(params, metas)
        b = [mt.pack(params, [m])[0] for m in metas]
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_pipeline_metas_all_packed_lane_aligned(self):
        metas = fp.pipeline_metas(make_params())
        assert all(not m.direct for m in metas)
        assert all(o % mt.LANE == 0 for m in metas for o in m.offsets)

    def test_packed_masters_roundtrip_and_pytree(self):
        params = make_params()
        pm = fp.pack_masters(params, params)
        rebuilt = pm.to_model(params)
        tree_bitwise(params, rebuilt)
        # pytree: tree_map preserves layout metadata
        pm2 = jax.tree_util.tree_map(lambda x: x * 2.0, pm)
        assert pm2.metas == pm.metas
        np.testing.assert_allclose(np.asarray(pm2.bufs[0]),
                                   2.0 * np.asarray(pm.bufs[0]))

    def test_packed_masters_flax_serialization_roundtrip(self):
        # the msgpack checkpoint path of examples/imagenet/main_amp.py
        from flax import serialization

        params = make_params()
        pm = fp.pack_masters(params, params)
        raw = serialization.to_bytes(pm)
        zero = jax.tree_util.tree_map(jnp.zeros_like, pm)
        back = serialization.from_bytes(zero, raw)
        assert back.metas == pm.metas
        tree_bitwise(back.bufs, pm.bufs)

    def test_grad_norm_finite_pallas_matches_jnp(self):
        params = make_params()
        metas = fp.pipeline_metas(params)
        gb = fp.pack_grads(params, metas)
        n_j, f_j = fp.grad_norm_finite(gb, 0.25, use_pallas=False)
        n_p, f_p = fp.grad_norm_finite(gb, 0.25, use_pallas=True)
        np.testing.assert_allclose(float(n_j), float(n_p), rtol=1e-6)
        assert bool(f_j) and bool(f_p)
        # reference value: 0.25 * ||tree||
        np.testing.assert_allclose(
            float(n_j), 0.25 * float(mt.l2norm(params)), rtol=1e-6)

    def test_grad_norm_finite_flags_nonfinite(self):
        bad = {"a": jnp.ones((40,)), "b": jnp.array([1.0, jnp.nan])}
        metas = fp.pipeline_metas(bad)
        gb = fp.pack_grads(bad, metas)
        for up in (False, True):
            _, fin = fp.grad_norm_finite(gb, 1.0, use_pallas=up)
            assert not bool(fin)


# ---------------------------------------------------------------------------
# Satellite: bitwise math-parity grid (ISSUE-4 acceptance)
# ---------------------------------------------------------------------------

ADAM_INNER = ((0.0, True), (0.01, True), (0.01, False), (0.0, False))


class TestAdamPipelineParity:
    """fp32/bf16/fp16 grads x adam_w_mode x weight_decay x
    bias_correction x clip, pipeline vs staged."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                       jnp.float16])
    @pytest.mark.parametrize("clip", [None, 0.05])
    def test_bitwise_vs_staged(self, dtype, clip):
        params = make_params()
        scale = 64.0
        policy = _policy(dtype, scale)
        for wd, bc in ADAM_INNER:
            mk = lambda: fused_adam(1e-2, weight_decay=wd,
                                    adam_w_mode=True,
                                    bias_correction=bc,
                                    max_grad_norm=clip)
            m1, s1, i1 = run_amp(mk, policy, params, pipeline=True)
            if clip is None:
                # clip off: fully independent staged path, bitwise
                m0, s0, _ = run_amp(mk, policy, params, pipeline=False)
                tree_bitwise(unpacked_masters(s1, params),
                             s0.master_params,
                             msg=f"masters {dtype} wd={wd} bc={bc}")
                tree_bitwise(
                    unpacked_state_bufs(s1.inner_state.m,
                                        s1.master_params.metas),
                    s0.inner_state.m, msg="m")
                tree_bitwise(
                    unpacked_state_bufs(s1.inner_state.v,
                                        s1.master_params.metas),
                    s0.inner_state.v, msg="v")
                tree_bitwise(m1, m0, msg="model")
            else:
                # clip on: the staged reference consumes the pipeline's
                # own combined inv*clip factor (reduction order of the
                # norm is the ONE legitimate difference); everything
                # downstream must then be bitwise
                m2, s2 = self._staged_combined_scale_reference(
                    params, policy, wd, bc, clip)
                tree_bitwise(unpacked_masters(s1, params), s2,
                             msg=f"masters(clip) {dtype} wd={wd}")
                tree_bitwise(m1, m2, msg="model(clip)")
                # and the independent staged amp path agrees to 1e-6
                m0, s0, _ = run_amp(mk, policy, params, pipeline=False)
                for a, b in zip(
                        jax.tree_util.tree_leaves(
                            unpacked_masters(s1, params)),
                        jax.tree_util.tree_leaves(s0.master_params)):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), rtol=1e-6,
                        atol=1e-7)
            assert i1.grad_norm is not None

    @staticmethod
    def _staged_combined_scale_reference(params, policy, wd, bc, clip,
                                         steps=3):
        """unscale+clip as ONE combined f32 factor (exactly as the
        update sweep applies it), then the staged fused_step on a
        masters pytree — the bitwise reference for clip-on configs."""
        tx = fused_adam(1e-2, weight_decay=wd, adam_w_mode=True,
                        bias_correction=bc)
        masters = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params)
        state = tx.init(masters)
        model = jax.tree_util.tree_map(
            lambda x: x.astype(policy.param_dtype), params)
        scale = policy.effective_loss_scale
        inv = jnp.float32(1.0 / scale)
        metas = fp.pipeline_metas(model)

        @jax.jit
        def step(g, state, masters):
            gb = fp.pack_grads(g, metas)
            gnorm, _ = fp.grad_norm_finite(gb, inv)
            combined = inv * _grad_clip_factor(gnorm, clip)
            g32 = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32) * combined, g)
            return tx.fused_step(g32, state, masters)

        for i in range(steps):
            g = grads_for(model, i, scale)
            masters, state, _ = step(g, state, masters)
            model = jax.tree_util.tree_map(
                lambda mm, x: x.astype(mm.dtype), model, masters)
        return model, masters

    def test_fp32_grads_on_fp16_model_not_downcast(self):
        """fp32 accumulated gradients against an fp16 model must reach
        the pipeline un-downcast: a 2^16-scaled fp32 grad cast to fp16
        would overflow to inf before the unscale sweep and stall
        training.  pack_grads keeps the widest member dtype; parity
        with the staged path stays bitwise."""
        params = make_params()
        policy = amp.get_policy("O2")  # fp16 model, dynamic 2^16 scale

        def run(pipeline):
            opt = amp.AmpOptimizer(fused_adam(1e-2), policy,
                                   pipeline=pipeline)
            state = opt.init(params)
            model = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float16), params)
            step = jax.jit(opt.apply_gradients)
            for i in range(2):
                # fp32 scaled grads large enough to overflow fp16
                g = jax.tree_util.tree_map(
                    lambda x: (x.astype(jnp.float32) * 2.0 + 1.0)
                    * float(state.scaler.loss_scale), model)
                model, state, info = step(g, state, model)
                assert bool(info.grads_finite)
            return model, state

        m1, s1 = run(True)
        m0, s0 = run(False)
        tree_bitwise(unpacked_masters(s1, params), s0.master_params)
        tree_bitwise(m1, m0)

    def test_static_scaling_elides_norm_sweep(self):
        """Static, unchecked scaling must not pay a grad-wide sweep
        (StepInfo.grad_norm None — the staged path elides its finite
        pass for the same measured reason); check_finite=True turns
        the sweep back on; optimizer-level clip still works without
        it, matching the staged clip within reduction-order ulps."""
        params = make_params()
        policy = _policy(jnp.bfloat16, 1.0)  # static scale, check=None
        mk = lambda: fused_adam(1e-2, max_grad_norm=0.05)
        opt = amp.AmpOptimizer(mk(), policy, pipeline=True)
        state = opt.init(params)
        model = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
        g = grads_for(model, 0, 1.0)
        _, _, info = jax.jit(opt.apply_gradients)(g, state, model)
        assert info.grad_norm is None and not info.grads_checked
        # the sweep runs when gradients are inspected
        m1, s1, info_c = run_amp(mk, policy, params, pipeline=True)
        assert info_c.grad_norm is not None
        # clip without the sweep == staged amp clip (tolerance: the
        # two norms reduce in different orders)
        def run_static(pipeline):
            o = amp.AmpOptimizer(mk(), policy, pipeline=pipeline)
            s = o.init(params)
            m = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), params)
            step = jax.jit(o.apply_gradients)
            for i in range(3):
                m, s, _ = step(grads_for(m, i, 1.0), s, m)
            return s
        s_p = run_static(True)
        s_s = run_static(False)
        for a, b in zip(
                jax.tree_util.tree_leaves(
                    unpacked_masters(s_p, params)),
                jax.tree_util.tree_leaves(s_s.master_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)

    def test_adam_l2_mode_bitwise(self):
        params = make_params()
        policy = _policy(jnp.bfloat16, 1.0)
        mk = lambda: fused_adam(1e-2, weight_decay=0.01,
                                adam_w_mode=False)
        m1, s1, _ = run_amp(mk, policy, params, pipeline=True)
        m0, s0, _ = run_amp(mk, policy, params, pipeline=False)
        tree_bitwise(unpacked_masters(s1, params), s0.master_params,
                     max_ulp=L2_CONTRACTION_ULP)
        tree_bitwise(m1, m0)

    def test_optax_chain_cross_check(self):
        """unscale -> clip -> optax.adamw reference (the ecosystem
        chain the pipeline replaces) agrees within fp32 roundoff."""
        params = make_params()
        policy = _policy(jnp.bfloat16, 64.0)
        clip = 0.05
        mk = lambda: fused_adam(1e-2, weight_decay=0.01,
                                max_grad_norm=clip)
        _, s1, _ = run_amp(mk, policy, params, pipeline=True)

        tx = optax.adamw(1e-2, b1=0.9, b2=0.999, eps=1e-8,
                         weight_decay=0.01)
        masters = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params)
        state = tx.init(masters)
        model = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
        for i in range(3):
            g = grads_for(model, i, 64.0)
            g32 = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32) / 64.0, g)
            gnorm = mt.l2norm(g32)
            factor = _grad_clip_factor(gnorm, clip)
            g32 = jax.tree_util.tree_map(lambda x: x * factor, g32)
            u, state = tx.update(g32, state, masters)
            masters = optax.apply_updates(masters, u)
            model = jax.tree_util.tree_map(
                lambda mm, x: x.astype(mm.dtype), model, masters)
        for a, b in zip(
                jax.tree_util.tree_leaves(unpacked_masters(s1, params)),
                jax.tree_util.tree_leaves(masters)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)


class TestSgdPipelineParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16,
                                       jnp.float16])
    def test_bitwise_vs_staged(self, dtype):
        params = make_params()
        policy = _policy(dtype, 64.0)
        for kw in ({"momentum": 0.9},
                   {"momentum": 0.9, "weight_decay": 0.01,
                    "dampening": 0.1},
                   {"momentum": 0.9, "nesterov": True},
                   {"momentum": 0.9, "weight_decay": 0.01,
                    "wd_after_momentum": True},
                   {"momentum": 0.0, "weight_decay": 0.01}):
            mk = lambda: fused_sgd(0.05, **kw)
            m1, s1, _ = run_amp(mk, policy, params, pipeline=True)
            m0, s0, _ = run_amp(mk, policy, params, pipeline=False)
            l2_lowp = (dtype != jnp.float32 and "weight_decay" in kw
                       and not kw.get("wd_after_momentum"))
            tree_bitwise(unpacked_masters(s1, params),
                         s0.master_params, msg=f"{dtype} {kw}",
                         max_ulp=L2_CONTRACTION_ULP if l2_lowp else 0)
            tree_bitwise(m1, m0, msg=f"model {kw}")


class TestLambPipeline:
    def test_matches_staged_within_reduction_order(self):
        """LAMB's trust-ratio reductions reduce in a different order
        over packed buffers (the clip-factor story again, per tensor)
        — parity is tight-tolerance, not bitwise."""
        params = make_params()
        policy = _policy(jnp.bfloat16, 1.0)
        mk = lambda: fused_lamb(1e-2, weight_decay=0.01,
                                max_grad_norm=1.0)
        m1, s1, i1 = run_amp(mk, policy, params, pipeline=True)
        m0, s0, _ = run_amp(mk, policy, params, pipeline=False)
        for a, b in zip(
                jax.tree_util.tree_leaves(unpacked_masters(s1, params)),
                jax.tree_util.tree_leaves(s0.master_params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        assert i1.grad_norm is not None

    def test_lamb_pipeline_state_packed(self):
        params = make_params()
        tx = fused_lamb(1e-2)
        metas = fp.pipeline_metas(params)
        st = tx.pipeline_init(metas)
        assert all(m.ndim == 1 and m.dtype == jnp.float32
                   for m in st.m)


# ---------------------------------------------------------------------------
# Pallas pipeline kernels (interpret mode) vs jnp twins
# ---------------------------------------------------------------------------

class TestPallasKernels:
    def test_adam_sgd_kernels_match_jnp(self):
        params = make_params()
        policy = _policy(jnp.bfloat16, 64.0)
        for mk_p, mk_j in (
                (lambda: fused_adam(1e-2, weight_decay=0.01,
                                    use_pallas=True),
                 lambda: fused_adam(1e-2, weight_decay=0.01,
                                    use_pallas=False)),
                (lambda: fused_sgd(0.05, momentum=0.9,
                                   use_pallas=True),
                 lambda: fused_sgd(0.05, momentum=0.9,
                                   use_pallas=False))):
            m_p, s_p, _ = run_amp(mk_p, policy, params, pipeline=True)
            m_j, s_j, _ = run_amp(mk_j, policy, params, pipeline=True)
            # interpret-mode kernels execute op-by-op while the jnp
            # twin compiles with FMA contraction — ulp-level drift is
            # expected across that boundary, bitwise is not
            for a, b in zip(
                    jax.tree_util.tree_leaves(
                        unpacked_masters(s_p, params)),
                    jax.tree_util.tree_leaves(
                        unpacked_masters(s_j, params))):
                np.testing.assert_allclose(np.asarray(a),
                                           np.asarray(b),
                                           rtol=1e-5, atol=1e-6)

    def test_self_check_runs(self):
        fp.self_check(steps=2)


# ---------------------------------------------------------------------------
# Overflow / nonfinite-grad branch
# ---------------------------------------------------------------------------

class TestOverflowBranch:
    def test_skip_is_bitwise_noop_and_backs_off(self):
        params = make_params()
        policy = amp.get_policy("O2")  # fp16, dynamic scaler
        opt = amp.AmpOptimizer(fused_adam(1e-2), policy, pipeline=True)
        state = opt.init(params)
        model = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float16), params)
        step = jax.jit(opt.apply_gradients)
        # one good step, then an overflow step
        g = grads_for(model, 0, float(state.scaler.loss_scale))
        model1, state1, info1 = step(g, state, model)
        assert bool(info1.grads_finite)
        bad = jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, jnp.inf), g)
        model2, state2, info2 = step(bad, state1, model1)
        assert not bool(info2.grads_finite)
        assert not bool(jnp.isfinite(info2.grad_norm))
        # masters/m/v/count/model bitwise unchanged
        tree_bitwise(state2.master_params, state1.master_params)
        tree_bitwise(state2.inner_state.m, state1.inner_state.m)
        tree_bitwise(state2.inner_state.v, state1.inner_state.v)
        assert int(state2.inner_state.count) == \
            int(state1.inner_state.count)
        tree_bitwise(model2, model1)
        # scaler backed off + skip counted
        assert float(state2.scaler.loss_scale) == \
            float(state1.scaler.loss_scale) * 0.5
        assert int(info2.steps_skipped) == 1

    def test_skip_matches_staged_path(self):
        params = make_params()
        policy = amp.get_policy("O2")

        def run(pipeline):
            opt = amp.AmpOptimizer(fused_adam(1e-2), policy,
                                   pipeline=pipeline)
            state = opt.init(params)
            model = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float16), params)
            step = jax.jit(opt.apply_gradients)
            for i in range(4):
                g = grads_for(model, i, float(state.scaler.loss_scale))
                if i == 2:  # inject an overflow mid-run
                    g = jax.tree_util.tree_map(
                        lambda x: x.at[(0,) * x.ndim].set(jnp.inf), g)
                model, state, info = step(g, state, model)
            return model, state

        m1, s1 = run(True)
        m0, s0 = run(False)
        tree_bitwise(unpacked_masters(s1, params), s0.master_params)
        tree_bitwise(m1, m0)
        assert float(s1.scaler.loss_scale) == \
            float(s0.scaler.loss_scale)
        assert int(s1.scaler.steps_skipped) == \
            int(s0.scaler.steps_skipped) == 1


# ---------------------------------------------------------------------------
# Escape hatch / wiring
# ---------------------------------------------------------------------------

class TestWiring:
    def test_env_escape_hatch(self):
        # no environment variable picks the route: the default and an
        # explicit pipeline=False step per leaf, pipeline=True packs
        policy = amp.get_policy("O5")
        params = {"w": jnp.ones((8, 128), jnp.float32)}
        for pipeline in (None, False):
            opt = amp.AmpOptimizer(fused_adam(1e-3), policy,
                                   pipeline=pipeline)
            assert not isinstance(opt.init(params).master_params,
                                  fp.PackedMasters)
        opt = amp.AmpOptimizer(fused_adam(1e-3), policy, pipeline=True)
        assert isinstance(opt.init(params).master_params,
                          fp.PackedMasters)

    def test_pack_min_bytes_small_tree_routes_direct(self):
        # the rule: the AUTO decision steps every tree per leaf, small
        # or large (the packed-size cutoff and its flag are gone);
        # explicit pipeline=True packs
        policy = amp.get_policy("O5")
        small = {"w": jnp.ones((64, 64), jnp.float32)}  # 8 KiB bf16
        large = {"w": jnp.zeros((4096, 8192), jnp.float32)}  # 64 MiB
        opt = amp.AmpOptimizer(fused_adam(1e-2), policy)
        for tree in (small, large):
            state = jax.eval_shape(opt.init, tree)
            assert not isinstance(state.master_params, fp.PackedMasters)
        forced = amp.AmpOptimizer(fused_adam(1e-2), policy,
                                  pipeline=True)
        assert isinstance(forced.init(small).master_params,
                          fp.PackedMasters)

    def test_pack_min_bytes_staged_state_steps(self):
        # an auto-routed (per-leaf) state must step through the
        # per-leaf path even though the optimizer is pipeline-capable —
        # the dispatch is on the state's layout, and the result matches
        # a pipeline=False optimizer bitwise
        policy = amp.get_policy("O5", loss_scale=256.0)
        params = {"w": jnp.linspace(-1.0, 1.0, 96,
                                    dtype=jnp.float32).reshape(8, 12)}
        model = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params)
        grads = jax.tree_util.tree_map(
            lambda x: (x * 0.01 * 256.0).astype(jnp.bfloat16), params)
        auto = amp.AmpOptimizer(fused_adam(1e-2), policy,
                                check_finite=True)
        staged = amp.AmpOptimizer(fused_adam(1e-2), policy,
                                  check_finite=True, pipeline=False)
        s_a, s_s = auto.init(params), staged.init(params)
        assert not isinstance(s_a.master_params, fp.PackedMasters)
        m_a, s_a, i_a = auto.apply_gradients(grads, s_a, model)
        m_s, s_s, i_s = staged.apply_gradients(grads, s_s, model)
        tree_bitwise(m_a, m_s)
        tree_bitwise(s_a.master_params, s_s.master_params)
        assert i_a.grad_norm is None and i_s.grad_norm is None

    def test_non_pipeline_tx_falls_back(self):
        # plain optax has no pipeline form; no masters -> no pipeline
        params = {"w": jnp.ones((8, 128), jnp.float32)}
        state = amp.AmpOptimizer(optax.sgd(0.1),
                                 amp.get_policy("O5")).init(params)
        assert not isinstance(state.master_params, fp.PackedMasters)
        assert amp.AmpOptimizer(fused_adam(1e-3), amp.get_policy(
            "O3")).init(params).master_params is None

    def test_explicit_pipeline_true_rejects_incapable_setups(self):
        # an explicit request must raise, not silently degrade to the
        # staged path (which would corrupt pipeline-vs-staged benches)
        with pytest.raises(ValueError, match="pipeline=True"):
            amp.AmpOptimizer(optax.sgd(0.1), amp.get_policy("O5"),
                             pipeline=True)
        with pytest.raises(ValueError, match="pipeline=True"):
            amp.AmpOptimizer(fused_adam(1e-3), amp.get_policy("O3"),
                             pipeline=True)

    def test_step_info_grad_norm_reused_by_monitor(self):
        from apex_tpu.amp.mixed_precision import StepInfo
        from apex_tpu.monitor import MemorySink, StepMonitor

        sink = MemorySink()
        mon = StepMonitor(sink)
        info = StepInfo(grads_finite=jnp.bool_(True),
                        loss_scale=jnp.float32(1.0),
                        steps_skipped=jnp.int32(0),
                        grads_checked=True,
                        grad_norm=jnp.float32(1.25))
        mon.start_step(0)
        mon.end_step(0, loss=0.5, scaler=info)
        mon.close()
        gn = [e for e in sink.by_kind("metric")
              if e.name == "grad_norm"]
        assert gn and gn[0].value == 1.25

    def test_train_smoke_same_loss_with_and_without_pipeline(
            self, monkeypatch):
        import functools

        from apex_tpu.testing import standalone_gpt
        from apex_tpu.testing.standalone_gpt import train_smoke

        loss_off = train_smoke(steps=4)
        monkeypatch.setattr(
            standalone_gpt, "make_smoke_setup",
            functools.partial(standalone_gpt.make_smoke_setup,
                              pipeline=True))
        loss_on = train_smoke(steps=4)
        np.testing.assert_allclose(loss_on, loss_off, rtol=1e-5)


# ---------------------------------------------------------------------------
# Satellite: packed persistent state survives checkpointing bitwise
# ---------------------------------------------------------------------------

class TestPackedCheckpoint:
    def _make(self, params):
        policy = amp.get_policy("O2")  # fp16 + dynamic scaler
        opt = amp.AmpOptimizer(fused_adam(1e-2, weight_decay=0.01),
                               policy, pipeline=True)
        state = opt.init(params)
        model = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float16), params)
        return opt, state, model

    def _steps(self, opt, state, model, n, start=0):
        step = jax.jit(opt.apply_gradients)
        for i in range(start, start + n):
            g = grads_for(model, i, float(state.scaler.loss_scale))
            model, state, _ = step(g, state, model)
        return state, model

    def test_save_restore_resume_bitwise(self, tmp_path):
        from apex_tpu.utils import CheckpointManager

        params = make_params()
        opt, state, model = self._make(params)
        state, model = self._steps(opt, state, model, 2)
        with CheckpointManager(str(tmp_path / "ck")) as mgr:
            mgr.save(2, model, opt, state)
        # fresh templates, restore, and compare everything bitwise
        opt2, state0, model0 = self._make(params)
        with CheckpointManager(str(tmp_path / "ck")) as mgr:
            model_r, state_r, _, step = mgr.restore(model0, opt2,
                                                    state0)
        assert step == 2
        assert isinstance(state_r.master_params, fp.PackedMasters)
        tree_bitwise(state_r.master_params, state.master_params)
        tree_bitwise(state_r.inner_state.m, state.inner_state.m)
        tree_bitwise(state_r.inner_state.v, state.inner_state.v)
        tree_bitwise(model_r, model)
        assert float(state_r.scaler.loss_scale) == \
            float(state.scaler.loss_scale)
        # resuming from the restore matches the uninterrupted run
        state_c, model_c = self._steps(opt, state, model, 2, start=2)
        state_r2, model_r2 = self._steps(opt2, state_r, model_r, 2,
                                         start=2)
        tree_bitwise(state_r2.master_params, state_c.master_params)
        tree_bitwise(model_r2, model_c)

    def test_torn_save_falls_back_to_previous_packed_step(
            self, tmp_path):
        from apex_tpu.resilience import corrupt_checkpoint
        from apex_tpu.utils import CheckpointManager, latest_valid_step

        params = make_params()
        opt, state, model = self._make(params)
        d = str(tmp_path / "ck")
        with CheckpointManager(d, keep=5) as mgr:
            state1, model1 = self._steps(opt, state, model, 1)
            mgr.save(1, model1, opt, state1)
            state2, model2 = self._steps(opt, state1, model1, 1,
                                         start=1)
            mgr.save(2, model2, opt, state2)
        corrupt_checkpoint(d, step=2, mode="truncate")
        assert latest_valid_step(d) == 2  # structurally sound, torn
        opt2, state0, model0 = self._make(params)
        with CheckpointManager(d) as mgr:
            model_r, state_r, _, step = mgr.restore(model0, opt2,
                                                    state0)
        assert step == 1  # deep fallback past the torn payload
        tree_bitwise(state_r.master_params, state1.master_params)
        tree_bitwise(model_r, model1)

    def test_mixed_mode_restore_is_a_clear_error_not_quarantine(
            self, tmp_path, monkeypatch):
        """A checkpoint saved in one master layout restored under the
        other must raise CheckpointFormatMismatch naming the mode —
        and must NOT be quarantined as a torn payload by the
        integrity fallback."""
        import os

        from apex_tpu.utils import (CheckpointFormatMismatch,
                                    CheckpointManager)

        params = make_params()
        opt, state, model = self._make(params)          # pipeline save
        state, model = self._steps(opt, state, model, 1)
        d = str(tmp_path / "ck")
        with CheckpointManager(d) as mgr:
            mgr.save(1, model, opt, state)
        # per-leaf templates against the packed-mode checkpoint
        policy = amp.get_policy("O2")
        opt0 = amp.AmpOptimizer(fused_adam(1e-2, weight_decay=0.01),
                                policy)
        state0 = opt0.init(params)
        assert not isinstance(state0.master_params, fp.PackedMasters)
        model0 = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float16), params)
        with CheckpointManager(d) as mgr:
            with pytest.raises(CheckpointFormatMismatch,
                               match=r"pipeline=True\) packs"):
                mgr.restore(model0, opt0, state0)
        # the good checkpoint survived untouched (no .corrupt rename)
        assert sorted(os.listdir(d)) == ["1"]
        # and the matching mode still restores it
        opt1, state1, model1 = self._make(params)
        with CheckpointManager(d) as mgr:
            _, state_r, _, step = mgr.restore(model1, opt1, state1)
        assert step == 1
        tree_bitwise(state_r.master_params, state.master_params)

    def test_kill_resume_equivalence_via_train_smoke(self, tmp_path,
                                                     monkeypatch):
        """The tier-1 resilience claim extended to the packed-state
        mode: kill@3 + resume == uninterrupted, bitwise on the packed
        masters.  The auto route steps every leaf where it lies, so
        the smoke's setup is built with pipeline=True: the loop runs
        the persistent pipeline this test exists to checkpoint."""
        import functools

        from apex_tpu.monitor import MemorySink
        from apex_tpu.resilience import parse_fault, run_resumable
        from apex_tpu.testing import standalone_gpt
        from apex_tpu.testing.standalone_gpt import train_smoke

        monkeypatch.setattr(
            standalone_gpt, "make_smoke_setup",
            functools.partial(standalone_gpt.make_smoke_setup,
                              pipeline=True))
        _, ref_params, ref_state, _ = train_smoke(steps=5,
                                                  return_state=True)
        assert isinstance(ref_state.master_params, fp.PackedMasters)
        mem = MemorySink()
        fault = parse_fault("crash@3")
        ck = str(tmp_path / "ck")

        def attempt(k):
            return train_smoke(steps=5, sink=mem, ckpt_dir=ck,
                               fault=fault, return_state=True)

        _, params2, state2, done = run_resumable(
            attempt, max_restarts=2, sink=mem, sleep=lambda s: None)
        assert done == 5
        tree_bitwise(ref_params, params2)
        tree_bitwise(ref_state.master_params, state2.master_params)
        assert float(ref_state.scaler.loss_scale) == \
            float(state2.scaler.loss_scale)


def test_norm_finite_pallas_matches_registered_twin():
    """Kernel-parity anchor: grad_norm_finite's Pallas sweep against
    the registered jnp twin _norm_finite_jnp, per buffer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.fused_pipeline import (_norm_finite_jnp,
                                             _norm_finite_pallas)

    buf = jax.random.normal(jax.random.PRNGKey(3), (640,)) * 7
    inv = jnp.float32(0.125)
    s_j, f_j = _norm_finite_jnp(buf, inv)
    s_p, f_p = _norm_finite_pallas(buf, inv, interpret=True)
    np.testing.assert_allclose(float(s_p), float(s_j), rtol=1e-6)
    assert bool(f_p) == bool(f_j) is True

    bad = buf.at[17].set(jnp.inf)
    s_j, f_j = _norm_finite_jnp(bad, inv)
    s_p, f_p = _norm_finite_pallas(bad, inv, interpret=True)
    assert bool(f_p) == bool(f_j) is False
