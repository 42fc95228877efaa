"""Flash attention parity vs the materializing reference implementation
(ref pattern: apex/contrib/test/fmha — fused vs unfused attention)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import (flash_attention,
                                          flash_attention_qkv,
                                          mha_reference)


def make_qkv(b=2, h=3, sq=128, sk=128, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, h, sk, d), dtype)
    v = jax.random.normal(ks[2], (b, h, sk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_parity(causal, dtype):
    q, k, v = make_qkv(dtype=dtype)
    got = flash_attention(q, k, v, causal=causal)
    want = mha_reference(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert got.dtype == dtype


def test_multi_kblock_and_unpadded_seq():
    # sk spans several 128-blocks and sq is not a block multiple.
    q, k, v = make_qkv(b=1, h=2, sq=200, sk=384, d=64)
    got = flash_attention(q, k, v, block_q=128, block_k=128)
    want = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_long_sequence_beyond_reference_cap():
    # The reference FMHA caps at seqlen 512 (ref: setup.py:408-424) and
    # fused softmax at 2048; flash handles longer.
    q, k, v = make_qkv(b=1, h=1, sq=2304, sk=2304, d=64)
    got = flash_attention(q, k, v, causal=True)
    want = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_cross_attention_shapes():
    q, k, v = make_qkv(sq=64, sk=256)
    got = flash_attention(q, k, v)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(mha_reference(q, k, v)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_parity(causal):
    q, k, v = make_qkv(b=1, h=2, sq=128, sk=128, d=64, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-3, atol=1e-4,
                                   err_msg=f"d{name}")


def test_backward_bf16():
    q, k, v = make_qkv(dtype=jnp.bfloat16, seed=5)
    g = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, causal=True).astype(jnp.float32)))(q)
    assert g.dtype == jnp.bfloat16
    gr = jax.grad(lambda q: jnp.sum(
        mha_reference(q, k, v, causal=True).astype(jnp.float32)))(q)
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(gr, np.float32),
                               rtol=1e-1, atol=1e-1)


def test_scale_default_is_rsqrt_d():
    q, k, v = make_qkv(d=64)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(flash_attention(q, k, v, scale=64 ** -0.5)),
        rtol=0, atol=0)


class TestKeyPaddingMask:
    """kv_mask (b, sk) padding-key support — a capability the
    reference's FMHA lacks (no mask arg, seqlen cap 512)."""

    @staticmethod
    def _mask(b, sk, seed=5):
        # at least one valid key per example
        lens = jax.random.randint(jax.random.PRNGKey(seed), (b,), 1,
                                  sk + 1)
        return (jnp.arange(sk)[None, :] < lens[:, None])

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity_masked(self, causal):
        q, k, v = make_qkv(b=3, h=2, sq=64, sk=64)
        m = self._mask(3, 64)
        got = flash_attention(q, k, v, causal=causal, kv_mask=m)
        want = mha_reference(q, k, v, causal=causal, kv_mask=m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("sq,sk", [(64, 64),        # fused bwd
                                       (2048, 2048)])   # two-kernel bwd
    def test_backward_parity_masked(self, sq, sk):
        q, k, v = make_qkv(b=2, h=2, sq=sq, sk=sk, seed=7)
        m = self._mask(2, sk)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, kv_mask=m) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, kv_mask=m) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3,
                                       err_msg=f"d{name}")

    def test_masked_keys_get_zero_grad(self):
        q, k, v = make_qkv(b=1, h=1, sq=32, sk=32, seed=9)
        m = jnp.arange(32)[None, :] < 20

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, kv_mask=m) ** 2)

        _, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_array_equal(np.asarray(dk[0, 0, 20:]), 0.0)
        np.testing.assert_array_equal(np.asarray(dv[0, 0, 20:]), 0.0)


class TestPackedQKV:
    """flash_attention_qkv(stack([q,k,v])) == flash_attention(q,k,v) —
    the packed entry reads q/k/v as row-ranges of ONE array (no
    per-tensor relayout copies at the custom-call boundary)."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("s", [128,      # single-block
                                   2048])    # two-kernel backward
    def test_forward_and_grad_parity(self, causal, s):
        q, k, v = make_qkv(b=2, h=2, sq=s, sk=s, seed=4)
        qkv = jnp.stack([q, k, v])

        def loss_packed(qkv):
            return jnp.sum(flash_attention_qkv(qkv, causal=causal) ** 2)

        def loss_ref(qkv):
            return jnp.sum(flash_attention(qkv[0], qkv[1], qkv[2],
                                           causal=causal) ** 2)

        np.testing.assert_allclose(
            np.asarray(flash_attention_qkv(qkv, causal=causal)),
            np.asarray(flash_attention(q, k, v, causal=causal)),
            rtol=2e-5, atol=2e-5)
        gp = jax.grad(loss_packed)(qkv)
        gr = jax.grad(loss_ref)(qkv)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)

    def test_kv_mask_parity(self):
        q, k, v = make_qkv(b=3, h=2, sq=64, sk=64, seed=6)
        qkv = jnp.stack([q, k, v])
        m = TestKeyPaddingMask._mask(3, 64)

        def loss_packed(qkv):
            return jnp.sum(flash_attention_qkv(qkv, kv_mask=m) ** 2)

        def loss_ref(qkv):
            return jnp.sum(flash_attention(qkv[0], qkv[1], qkv[2],
                                           kv_mask=m) ** 2)

        np.testing.assert_allclose(
            np.asarray(flash_attention_qkv(qkv, kv_mask=m)),
            np.asarray(flash_attention(q, k, v, kv_mask=m)),
            rtol=2e-5, atol=2e-5)
        gp = jax.grad(loss_packed)(qkv)
        gr = jax.grad(loss_ref)(qkv)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-3, atol=1e-3)

    def test_unaligned_seq(self):
        q, k, v = make_qkv(b=1, h=2, sq=200, sk=200, seed=8)
        qkv = jnp.stack([q, k, v])
        got = flash_attention_qkv(qkv, block_q=128, block_k=128)
        want = mha_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_short_seq_default_blocks_with_mask(self):
        # s=50 under DEFAULT blocks once exploded to lcm(50,128)=3200
        # padded rows and crashed _kvm8's reshape; blocks must clamp to
        # the 128-lane grain instead.
        q, k, v = make_qkv(b=2, h=2, sq=50, sk=50, seed=10)
        qkv = jnp.stack([q, k, v])
        m = jnp.arange(50)[None, :] < jnp.asarray([[50], [30]])

        def loss(qkv):
            return jnp.sum(flash_attention_qkv(qkv, kv_mask=m) ** 2)

        got = flash_attention_qkv(qkv, kv_mask=m)
        want = mha_reference(q, k, v, kv_mask=m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        g = jax.grad(loss)(qkv)
        assert np.isfinite(np.asarray(g)).all()


def test_fully_masked_rows_zero_output_and_grads():
    """A query row whose keys are ALL masked must produce exactly zero
    output and zero gradients (forward and backward agree)."""
    q, k, v = make_qkv(b=1, h=1, sq=16, sk=16, seed=11)
    m = jnp.zeros((1, 16), bool).at[0, 8:].set(True)  # leading keys off

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, kv_mask=m)
        return jnp.sum(o ** 2), o

    (l, o), (dq, dk, dv) = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    # causal rows 0..7 can only see masked keys -> exact zeros
    np.testing.assert_array_equal(np.asarray(o[0, 0, :8]), 0.0)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_array_equal(np.asarray(dq[0, 0, :8]), 0.0)
    assert np.isfinite(np.asarray(dq)).all()
    assert np.isfinite(np.asarray(dk)).all()
    assert np.isfinite(np.asarray(dv)).all()


class TestHeadPackedD64:
    """d=64 head-pair packing (the round-6 full-width MXU path): two
    heads share one 128-lane tile and the kernels recover per-head
    scores via the sigma rotation.  Parity vs the jnp reference AND vs
    the forced-unpacked kernels at the SAME tolerances as the d=128
    path, across the fused single-block backward and both two-pass
    backward kernels, causal and non-causal, with and without the
    kv_mask segment masking, plus the partial (ring) entry and
    in-kernel dropout."""

    @pytest.fixture(autouse=True)
    def _restore_packing(self):
        from apex_tpu.ops import flash_attention as fa
        assert fa.head_packing_enabled()   # default ON
        yield
        fa.set_head_packing(True)

    @staticmethod
    def _unpacked(fn, *args, **kw):
        from apex_tpu.ops import flash_attention as fa
        fa.set_head_packing(False)
        try:
            return fn(*args, **kw)
        finally:
            fa.set_head_packing(True)

    def test_dispatch_predicate(self):
        from apex_tpu.ops.flash_attention import _use_head_packing
        assert _use_head_packing(2, 64) and _use_head_packing(16, 64)
        assert not _use_head_packing(3, 64)    # odd h
        assert not _use_head_packing(16, 128)  # already full-width
        assert not _use_head_packing(16, 32)

    def test_escape_hatch(self):
        from apex_tpu.ops import flash_attention as fa
        fa.set_head_packing(False)
        assert not fa.head_packing_enabled()
        assert not fa._use_head_packing(16, 64)
        fa.set_head_packing(True)
        assert fa._use_head_packing(16, 64)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_forward_parity_fused(self, causal, dtype):
        # h even + d=64 -> packed; single-block forward kernel
        q, k, v = make_qkv(b=2, h=4, sq=128, sk=128, dtype=dtype, seed=1)
        got = flash_attention(q, k, v, causal=causal)
        want = mha_reference(q, k, v, causal=causal)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_parity_grid(self, causal):
        # multi-block online-softmax kernel, unaligned sq + cross attn
        q, k, v = make_qkv(b=1, h=2, sq=200, sk=384, seed=2)
        got = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("masked", [False, True])
    def test_backward_parity_fused_kernel(self, causal, masked):
        # s=128 at default blocks -> the packed _bwd_fused_kernel
        q, k, v = make_qkv(b=2, h=2, sq=128, sk=128, seed=3)
        m = TestKeyPaddingMask._mask(2, 128) if masked else None

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           kv_mask=m) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal,
                                         kv_mask=m) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("masked", [False, True])
    def test_backward_parity_two_pass_kernels(self, causal, masked):
        # 128-blocks over s=320 -> the packed _bwd_dq + _bwd_dkv pair
        q, k, v = make_qkv(b=1, h=2, sq=320, sk=320, seed=4)
        m = TestKeyPaddingMask._mask(1, 320) if masked else None

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           kv_mask=m, block_q=128,
                                           block_k=128) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal,
                                         kv_mask=m) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-3, atol=1e-3,
                                       err_msg=f"d{name}")

    def test_packed_matches_forced_unpacked(self):
        """The escape hatch selects a different kernel layout, not a
        different computation: outputs and gradients agree to fp
        reassociation noise."""
        q, k, v = make_qkv(b=1, h=4, sq=256, sk=256, seed=5)

        def run(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=128,
                                   block_k=128)

        def loss(q, k, v):
            return jnp.sum(run(q, k, v) ** 2)

        got = run(q, k, v)
        want = self._unpacked(run, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gu = self._unpacked(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
        for a, b_, name in zip(gp, gu, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")

    def test_partial_entry_offsets_and_lse(self):
        """The ring building block: packed partial (o, lse) at traced
        GLOBAL offsets — o, lse AND the lse-cotangent gradients match
        the forced-unpacked kernels."""
        from apex_tpu.ops.flash_attention import flash_attention_partial
        s = 128
        q, k, v = make_qkv(b=1, h=2, sq=s, sk=s, seed=6)

        def partial(q, k, v):
            return flash_attention_partial(
                q, k, v, causal=True, q_offset=jnp.int32(s),
                k_offset=jnp.int32(0))

        def loss(q, k, v):
            o, lse = partial(q, k, v)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

        (op, lp) = partial(q, k, v)
        (ou, lu) = self._unpacked(partial, q, k, v)
        np.testing.assert_allclose(np.asarray(op), np.asarray(ou),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lu),
                                   rtol=2e-5, atol=2e-5)
        gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gu = self._unpacked(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
        for a, b_, name in zip(gp, gu, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")

    def test_fully_future_block_is_dead(self):
        """A packed ring block entirely in the causal future emits
        exactly 0 with an annihilating lse (the merge contract)."""
        from apex_tpu.ops.flash_attention import flash_attention_partial
        s = 128
        q, k, v = make_qkv(b=1, h=2, sq=s, sk=s, seed=7)
        o, lse = flash_attention_partial(
            q, k, v, causal=True, q_offset=jnp.int32(0),
            k_offset=jnp.int32(s))
        np.testing.assert_array_equal(np.asarray(o), 0.0)
        assert float(np.asarray(lse).max()) < -1e28

    def test_in_kernel_dropout_mask_is_layout_invariant(self):
        """The coordinate-hash keep mask is a function of GLOBAL
        (seed, head, row, col) — packed and unpacked kernels must drop
        the SAME entries, so outputs and gradients agree."""
        from apex_tpu.ops.flash_attention import flash_attention_partial
        s, rate, seed = 128, 0.3, 1234
        q, k, v = make_qkv(b=1, h=2, sq=s, sk=s, seed=8)

        def drop(q, k, v):
            return flash_attention_partial(
                q, k, v, causal=True, q_offset=jnp.int32(s),
                k_offset=jnp.int32(0), dropout_rate=rate,
                dropout_seed=seed, head_offset=4)[0]

        got = drop(q, k, v)
        want = self._unpacked(drop, q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        gp = jax.grad(lambda q: jnp.sum(drop(q, k, v) ** 2))(q)
        gu = self._unpacked(
            jax.grad(lambda q: jnp.sum(drop(q, k, v) ** 2)), q)
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gu),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16_backward(self):
        q, k, v = make_qkv(b=1, h=2, sq=128, sk=128,
                           dtype=jnp.bfloat16, seed=9)
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=True)
            .astype(jnp.float32)))(q)
        assert g.dtype == jnp.bfloat16
        gr = jax.grad(lambda q: jnp.sum(
            mha_reference(q, k, v, causal=True)
            .astype(jnp.float32)))(q)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(gr, np.float32),
                                   rtol=1e-1, atol=1e-1)


class TestELayout:
    """flash_attention_e: the projection-native (b, s, h, 3d) entry —
    no relayout copies at the attention boundary."""

    @staticmethod
    def _ref(qkv, causal=False, kv_mask=None):
        b, s, h, td = qkv.shape
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        o = mha_reference(q, k, v, causal=causal, kv_mask=kv_mask)
        return o.transpose(0, 2, 1, 3).reshape(b, s, h * (td // 3))

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", [(2, 128, 4, 64),
                                       (2, 200, 4, 64),    # padded s
                                       (1, 256, 8, 32),    # d=32 grouping
                                       (2, 128, 6, 64)])   # hg=2
    def test_forward_and_grad_parity(self, causal, shape):
        from apex_tpu.ops.flash_attention import flash_attention_e
        b, s, h, d = shape
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))

        def loss_e(qkv):
            return jnp.sum(flash_attention_e(qkv, causal=causal) * w)

        def loss_r(qkv):
            return jnp.sum(self._ref(qkv, causal=causal) * w)

        got = flash_attention_e(qkv, causal=causal)
        want = self._ref(qkv, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        ge = jax.grad(loss_e)(qkv)
        gr = jax.grad(loss_r)(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("s", [128, 200])
    def test_kv_mask_parity(self, s):
        from apex_tpu.ops.flash_attention import flash_attention_e
        b, h, d = 2, 4, 64
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        lens = jnp.array([s // 2, s])
        m = jnp.arange(s)[None, :] < lens[:, None]
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))

        got = flash_attention_e(qkv, kv_mask=m)
        want = self._ref(qkv, kv_mask=m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

        def loss_e(qkv):
            return jnp.sum(flash_attention_e(qkv, kv_mask=m) * w)

        def loss_r(qkv):
            return jnp.sum(self._ref(qkv, kv_mask=m) * w)

        ge = jax.grad(loss_e)(qkv)
        gr = jax.grad(loss_r)(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("shape", [(1, 512, 4, 64),    # 2 x 256
                                       (1, 640, 2, 64),    # 5 x 128
                                       (1, 1000, 2, 64),   # padded: 4 x 256
                                       (1, 1024, 4, 64),   # GPT-2's block
                                       (1, 768, 8, 32)])   # 3 x 256, hg=8
    def test_causal_row_chunks_parity(self, shape):
        """Causal one-block kernels walk the triangle in row chunks
        (``_e_chunk``): same outputs and gradients as the dense
        reference at sizes with two to five chunks."""
        from apex_tpu.ops.flash_attention import (flash_attention_e,
                                                  flash_e_plan)
        b, s, h, d = shape
        plan = flash_e_plan(s, h, d, True)
        assert plan.mode == "single" and plan.share < 1.0
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        got = flash_attention_e(qkv, causal=True)
        want = self._ref(qkv, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        ge = jax.grad(lambda q: jnp.sum(
            flash_attention_e(q, causal=True) * w))(qkv)
        gr = jax.grad(lambda q: jnp.sum(
            self._ref(q, causal=True) * w))(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)

    def test_causal_row_chunks_kv_mask(self):
        from apex_tpu.ops.flash_attention import flash_attention_e
        b, s, h, d = 2, 512, 2, 64
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        lens = jnp.array([300, s])         # the cut inside chunk 1
        m = jnp.arange(s)[None, :] < lens[:, None]
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        got = flash_attention_e(qkv, causal=True, kv_mask=m)
        want = self._ref(qkv, causal=True, kv_mask=m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        ge = jax.grad(lambda q: jnp.sum(flash_attention_e(
            q, causal=True, kv_mask=m) * w))(qkv)
        gr = jax.grad(lambda q: jnp.sum(self._ref(
            q, causal=True, kv_mask=m) * w))(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("shape,causal,want", [
        # the benchmark's training cells
        ((1024, 16, 64), True, ("single", 4, 256, 0.625)),
        ((512, 16, 64), False, ("single", 4, 512, 1.0)),
        ((512, 16, 64), True, ("single", 4, 256, 0.75)),
        ((640, 16, 64), True, ("single", 4, 128, 0.6)),
        ((1000, 16, 64), True, ("single", 4, 256, 0.625)),
        ((256, 8, 32), True, ("single", 8, 128, 0.75)),
        ((128, 4, 64), True, ("single", 4, 128, 1.0)),
        ((2048, 16, 64), True, ("blocked", 4, 512, 0.625)),
        ((2048, 16, 64), False, ("blocked", 4, 512, 1.0)),
        ((65536, 16, 64), True, (None, None, None, None)),
    ])
    def test_plan(self, shape, causal, want):
        from apex_tpu.ops.flash_attention import flash_e_plan
        assert tuple(flash_e_plan(*shape, causal)) == want
        if want[0] == "single" and shape[0] == 1024:
            # in-kernel dropout halves the heads a step, as _e_mode says
            assert flash_e_plan(*shape, causal, drop=True).hg == 2

    @staticmethod
    def _kernel_jaxprs(s, h, d, causal, masked=False):
        """(forward, backward) kernel jaxprs of one flash_attention_e
        call under ``jax.grad``, traced and never run."""
        from apex_tpu.ops.flash_attention import flash_attention_e
        m = jnp.ones((1, s), bool) if masked else None

        def found(jaxpr, into):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    into.append(eqn.params["jaxpr"])
                for v in eqn.params.values():
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        found(inner, into)
            return into

        top = jax.make_jaxpr(jax.grad(lambda x: flash_attention_e(
            x, causal=causal, kv_mask=m).astype(jnp.float32).sum()))(
            jax.ShapeDtypeStruct((1, s, h, 3 * d), jnp.bfloat16))
        fwd, bwd = found(top.jaxpr, [])
        return fwd, bwd

    @staticmethod
    def _multiply_adds(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (lc, rc), _ = eqn.params["dimension_numbers"]
                lhs, rhs = (v.aval.shape for v in eqn.invars)
                total += int(np.prod(lhs)) * int(np.prod(
                    [n for i, n in enumerate(rhs) if i not in rc]))
        return total

    @pytest.mark.parametrize("s", [1024, 1000, 640])
    def test_causal_kernels_do_the_plans_share_of_the_work(self, s):
        """The matmuls of the causal kernels are the plan's share of the
        square's: two (c, hi, d) products a chunk forward, five
        backward."""
        from apex_tpu.ops.flash_attention import flash_e_plan
        h, d = 16, 64
        plan = flash_e_plan(s, h, d, True)
        ps = -(-s // 128) * 128
        fwd, bwd = self._kernel_jaxprs(s, h, d, True)
        square = plan.hg * ps * ps * d
        assert self._multiply_adds(fwd) == round(2 * square * plan.share)
        assert self._multiply_adds(bwd) == round(5 * square * plan.share)

    @pytest.mark.parametrize("masked,eqns", [(False, (107, 116)),
                                             (True, (126, 123))])
    def test_noncausal_kernels_keep_their_bodies(self, masked, eqns):
        """``causal=False`` (BERT's route) runs the one-chunk body: the
        equation counts read on the parent of PR 32, and the whole
        square's multiply-adds."""
        s, h, d = 512, 16, 64
        fwd, bwd = self._kernel_jaxprs(s, h, d, False, masked)
        assert (len(fwd.eqns), len(bwd.eqns)) == eqns
        assert self._multiply_adds(fwd) == 2 * 4 * s * s * d
        assert self._multiply_adds(bwd) == 5 * 4 * s * s * d

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", [(1, 1152, 2, 64),   # padded s
                                       (1, 2048, 4, 64),
                                       (1, 1536, 3, 128)])  # odd h, hg=1
    def test_blocked_long_sequence(self, causal, shape):
        """ps > 1024 streams (bs, bs) tiles — same zero-relayout layout,
        online softmax, one-kernel combined backward."""
        from apex_tpu.ops.flash_attention import (flash_attention_e,
                                                  flash_e_supported)
        b, s, h, d = shape
        assert flash_e_supported(s, h, d)
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        got = flash_attention_e(qkv, causal=causal)
        want = self._ref(qkv, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

        def loss_e(qkv):
            return jnp.sum(flash_attention_e(qkv, causal=causal) * w)

        def loss_r(qkv):
            return jnp.sum(self._ref(qkv, causal=causal) * w)

        ge = jax.grad(loss_e)(qkv)
        gr = jax.grad(loss_r)(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=5e-4, atol=5e-4)

    def test_blocked_kv_mask(self):
        from apex_tpu.ops.flash_attention import flash_attention_e
        b, s, h, d = 2, 1536, 2, 64
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        lens = jnp.array([700, s])
        m = jnp.arange(s)[None, :] < lens[:, None]
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        got = flash_attention_e(qkv, kv_mask=m)
        want = self._ref(qkv, kv_mask=m)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        ge = jax.grad(lambda q: jnp.sum(
            flash_attention_e(q, kv_mask=m) * w))(qkv)
        gr = jax.grad(lambda q: jnp.sum(self._ref(q, kv_mask=m) * w))(
            qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=5e-4, atol=5e-4)

    def test_very_long_sequence_falls_back(self):
        from apex_tpu.ops.flash_attention import (_E_MAX_SEQ_BLOCKED,
                                                  flash_e_supported)
        assert not flash_e_supported(_E_MAX_SEQ_BLOCKED + 128, 4, 64)

    def test_e_mode_routes_s16384_blocked(self):
        """Round-5: the blocked walk owns s=16384 for BOTH head dims —
        no transposing-path fallback on the framework's scaling axis.
        (Numeric parity at this length is hardware-verified:
        tools/hw_checks/flash_e_s16384.py, grad maxabs diff <= 2e-3 in
        bf16 vs the independently-implemented transposing kernels.)"""
        from apex_tpu.ops.flash_attention import _e_mode
        for h, d in ((16, 64), (16, 128), (8, 64), (8, 128)):
            mode, hg = _e_mode(16384, h, d)
            assert mode == "blocked", (h, d, mode, hg)
            assert h % hg == 0 and (3 * hg * d) % 128 == 0
            # dropout configs stay eligible too (halved temp budget)
            mode_d, _ = _e_mode(16384, h, d, drop=True)
            assert mode_d == "blocked", (h, d, mode_d)

    def test_grouping_helper(self):
        from apex_tpu.ops.flash_attention import _pick_heads_per_group
        assert _pick_heads_per_group(16, 64, 1024) == 4  # 3*4*64 = 768
        assert _pick_heads_per_group(6, 64, 1024) == 2   # 3*2*64 = 384
        assert _pick_heads_per_group(8, 32, 256) == 8    # 3*8*32 = 768
        # score-temp cap: tiny d would pack every head into one group
        # and blow VMEM on the unrolled (ps, ps) fp32 temps
        assert _pick_heads_per_group(16, 16, 1024) is None
        # no divisor of h makes 3*hg*d lane-aligned -> None
        assert _pick_heads_per_group(5, 24, 128) is None


class TestELayoutDropout:
    """In-kernel attention dropout on the E route: the keep mask is a
    deterministic counter-hash of (seed, batch, head, q-block, k-block),
    so a dense reference can regenerate the EXACT mask and the kernel
    must match it bitwise-in-expectation — forward and gradients."""

    @staticmethod
    def _dense_with_mask(qkv, keep, rate, causal):
        b, s, h, td = qkv.shape
        d = td // 3
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.transpose(0, 2, 1, 3).astype(jnp.float32)
                   for t in (q, k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                               scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        pd = jnp.where(keep, p, 0.0) / (1.0 - rate)
        o = jnp.einsum("bhqk,bhkd->bhqd", pd.astype(qkv.dtype)
                       .astype(jnp.float32), v)
        return o.astype(qkv.dtype).transpose(0, 2, 1, 3).reshape(
            b, s, h * d)

    @staticmethod
    def _expected_keep(b, h, s, seed, rate, bs):
        """Reassemble the kernels' keep mask outside the kernel."""
        from apex_tpu.ops.flash_attention import _rand_keep
        nb = -(-s // bs)
        ps = nb * bs
        keep = np.ones((b, h, ps, ps), bool)
        for bi in range(b):
            for hh in range(h):
                for i in range(nb):
                    for j in range(nb):
                        blk = _rand_keep((bs, bs), seed, bi, hh, i, j,
                                         rate)
                        keep[bi, hh, i * bs:(i + 1) * bs,
                             j * bs:(j + 1) * bs] = np.asarray(blk)
        return jnp.asarray(keep[:, :, :s, :s])

    @pytest.mark.parametrize("causal", [False, True])
    def test_single_block_dropout_parity(self, causal):
        from apex_tpu.ops.flash_attention import flash_attention_e
        b, s, h, d, rate = 2, 128, 4, 64, 0.3
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        seed = 1234
        # single-block path: one (ps, ps) tile, salts (i, j) = (0, 0)
        keep = self._expected_keep(b, h, s, seed, rate, bs=s)
        got = flash_attention_e(qkv, causal=causal, dropout_rate=rate,
                                dropout_seed=seed)
        want = self._dense_with_mask(qkv, keep, rate, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

        ge = jax.grad(lambda x: jnp.sum(flash_attention_e(
            x, causal=causal, dropout_rate=rate, dropout_seed=seed)
            * w))(qkv)
        gr = jax.grad(lambda x: jnp.sum(self._dense_with_mask(
            x, keep, rate, causal) * w))(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=5e-4, atol=5e-4)

    def test_causal_row_chunks_dropout_parity(self):
        """Two row chunks at s=512: forward, backward and the dense
        reference agree on ONE keep mask, the (ps, ps) tile's of a
        kernel that computes the whole square."""
        from apex_tpu.ops.flash_attention import flash_attention_e
        b, s, h, d, rate = 1, 512, 2, 64, 0.3
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        seed = 4321
        keep = self._expected_keep(b, h, s, seed, rate, bs=s)
        got = flash_attention_e(qkv, causal=True, dropout_rate=rate,
                                dropout_seed=seed)
        want = self._dense_with_mask(qkv, keep, rate, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        ge = jax.grad(lambda x: jnp.sum(flash_attention_e(
            x, causal=True, dropout_rate=rate, dropout_seed=seed)
            * w))(qkv)
        gr = jax.grad(lambda x: jnp.sum(self._dense_with_mask(
            x, keep, rate, True) * w))(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=5e-4, atol=5e-4)

    def test_blocked_dropout_parity(self):
        from apex_tpu.ops.flash_attention import (_E_BLOCK,
                                                  flash_attention_e)
        b, s, h, d, rate = 1, 1536, 2, 64, 0.2
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        seed = 77
        keep = self._expected_keep(b, h, s, seed, rate,
                                   bs=min(_E_BLOCK, s))
        got = flash_attention_e(qkv, causal=True, dropout_rate=rate,
                                dropout_seed=seed)
        want = self._dense_with_mask(qkv, keep, rate, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        ge = jax.grad(lambda x: jnp.sum(flash_attention_e(
            x, causal=True, dropout_rate=rate, dropout_seed=seed)
            * w))(qkv)
        gr = jax.grad(lambda x: jnp.sum(self._dense_with_mask(
            x, keep, rate, True) * w))(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=5e-4, atol=5e-4)

    @pytest.mark.parametrize("s", [128, 1536])   # single-block, blocked
    def test_kv_mask_with_dropout_parity(self, s):
        from apex_tpu.ops.flash_attention import (_E_BLOCK, _E_MAX_SEQ,
                                                  flash_attention_e)
        b, h, d, rate = 2, 2, 64, 0.25
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (b, s, h, 3 * d)) * 0.5
        lens = jnp.array([s // 2, s])
        m = jnp.arange(s)[None, :] < lens[:, None]
        w = jax.random.normal(jax.random.PRNGKey(1), (b, s, h * d))
        seed = 99
        bs = s if s <= _E_MAX_SEQ else min(_E_BLOCK, s)
        keep = self._expected_keep(b, h, s, seed, rate, bs=bs)

        def dense(x):
            bq, sq, hq, td = x.shape
            dq = td // 3
            q, k, v = jnp.split(x, 3, axis=-1)
            q, k, v = (t.transpose(0, 2, 1, 3).astype(jnp.float32)
                       for t in (q, k, v))
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (dq ** -0.5)
            scores = jnp.where(m[:, None, None, :], scores, -1e30)
            p = jax.nn.softmax(scores, axis=-1)
            pd = jnp.where(keep, p, 0.0) / (1.0 - rate)
            o = jnp.einsum("bhqk,bhkd->bhqd", pd, v)
            return o.astype(x.dtype).transpose(0, 2, 1, 3).reshape(
                bq, sq, hq * dq)

        got = flash_attention_e(qkv, kv_mask=m, dropout_rate=rate,
                                dropout_seed=seed)
        want = dense(qkv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        ge = jax.grad(lambda x: jnp.sum(flash_attention_e(
            x, kv_mask=m, dropout_rate=rate, dropout_seed=seed) * w))(
            qkv)
        gr = jax.grad(lambda x: jnp.sum(dense(x) * w))(qkv)
        np.testing.assert_allclose(np.asarray(ge), np.asarray(gr),
                                   rtol=7e-4, atol=7e-4)

    def test_short_seq_small_d_routes_blocked(self):
        """h=16/d=16 at s=1024: the whole-block grouping misfits VMEM
        but the (bs, bs) blocked walk qualifies — no transposing
        fallback at short sequences of an eligible shape."""
        from apex_tpu.ops.flash_attention import _e_mode, \
            flash_attention_e
        mode, hg = _e_mode(1024, 16, 16)
        assert mode == "blocked"
        qkv = jax.random.normal(jax.random.PRNGKey(0),
                                (1, 1024, 16, 48)) * 0.5
        got = flash_attention_e(qkv, causal=True)
        want = TestELayout._ref(qkv, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_dropout_statistics_and_determinism(self):
        from apex_tpu.ops.flash_attention import flash_attention_e
        b, s, h, d, rate = 1, 256, 4, 64, 0.5
        qkv = jnp.ones((b, s, h, 3 * d)) * 0.1
        o1 = flash_attention_e(qkv, dropout_rate=rate, dropout_seed=3)
        o2 = flash_attention_e(qkv, dropout_rate=rate, dropout_seed=3)
        o3 = flash_attention_e(qkv, dropout_rate=rate, dropout_seed=4)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        assert np.abs(np.asarray(o1) - np.asarray(o3)).max() > 0
        # E[dropout(P)] = P: with uniform inputs the mean output stays
        # ~the no-dropout value
        o0 = flash_attention_e(qkv)
        assert abs(float(jnp.mean(o1)) - float(jnp.mean(o0))) \
            < 5e-2 * abs(float(jnp.mean(o0))) + 1e-3

    def test_seed_required(self):
        from apex_tpu.ops.flash_attention import flash_attention_e
        qkv = jnp.ones((1, 128, 4, 192))
        with pytest.raises(ValueError, match="dropout_seed"):
            flash_attention_e(qkv, dropout_rate=0.1)
