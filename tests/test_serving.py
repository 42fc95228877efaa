"""Serving-stack tests (ISSUE-9): flash-decode kernel parity vs the
jnp twin, KV paging invariants, continuous-batching determinism,
bucket-ladder compile discipline, and the clean-drain contract.

The parity anchor the audit (APX402) pins ``ops/flash_decode.py`` to:
:func:`flash_decode` vs :func:`paged_attention_reference` on randomly
paged caches — unpacked, head-packed d=64, and int8 weight-only
layouts, with inactive rows, straddling pages, and dump-page padding
in every case.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import set_head_packing
from apex_tpu.ops.flash_decode import (flash_decode,
                                       flash_decode_multi,
                                       pack_decode_heads,
                                       paged_attention_multi_reference,
                                       paged_attention_reference,
                                       unpack_decode_heads,
                                       use_decode_head_packing)
from apex_tpu.serving import (DUMP_BLOCK, BucketLadder,
                              CachePoolExhausted, KVCacheConfig,
                              KVCacheManager, Request, ServingEngine,
                              ServingModelConfig, default_cache_config,
                              extract_serving_weights, init_cache,
                              plan_page_write, quantize_kv_rows,
                              write_prefill_kv, write_token_kv)
from apex_tpu.testing.standalone_gpt import GPTModel, serve_smoke


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _pack_cache(dense):
    """dense (nb, h, bs, d) -> packed storage (nb, h/2, bs, 2d)."""
    return pack_decode_heads(dense.transpose(0, 2, 1, 3)) \
        .transpose(0, 2, 1, 3)


def make_paged_case(b=3, h=2, d=32, nb=8, bs=8, mp=3, *, seed=0,
                    dtype=jnp.float32, packed=False, int8=False):
    """Random q + paged cache + block tables with the hard cases baked
    in: row 0 inactive (seq_len 0, all-dump table), row 1 straddling a
    page mid-block, row 2 exactly filling its pages."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k_dense = jax.random.normal(ks[1], (nb, h, bs, d), dtype)
    v_dense = jax.random.normal(ks[2], (nb, h, bs, d), dtype)
    rng = np.random.RandomState(seed)
    bt = np.full((b, mp), DUMP_BLOCK, np.int32)
    sl = np.zeros(b, np.int32)
    # rows after 0 get distinct non-dump blocks, lengths cycling over
    # straddle / exact-fill / short
    pool = rng.permutation(np.arange(1, nb))
    lens = [0, mp * bs - bs // 2 - 1, mp * bs] + \
        [1 + rng.randint(mp * bs) for _ in range(b - 3)]
    nxt = 0
    for i in range(1, b):
        sl[i] = lens[i % len(lens)] if i < len(lens) else lens[i]
        pages = -(-int(sl[i]) // bs)
        bt[i, :pages] = pool[nxt:nxt + pages]
        nxt += pages
    ksc = vsc = None
    if int8:
        k_dense, ksc = quantize_kv_rows(k_dense)
        v_dense, vsc = quantize_kv_rows(v_dense)
        ksc = ksc.transpose(0, 1, 2)              # (nb, h, bs)
        vsc = vsc.transpose(0, 1, 2)
    if packed:
        k_cache, v_cache = _pack_cache(k_dense), _pack_cache(v_dense)
    else:
        k_cache, v_cache = k_dense, v_dense
    return (q, k_cache, v_cache, jnp.asarray(bt), jnp.asarray(sl),
            ksc, vsc)


def make_ragged_case(lens, layout, *, h=4, d=64, nb=64, bs=8, mp=4,
                     seed=0):
    """q + paged cache in one of the four storage layouts, one row a
    length in ``lens``; returns (q, k, v, tables, seq_lens, scales)."""
    b = len(lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    # the cache is the same for every seed, so that a request keeps
    # its pages' contents from one case to the next
    kc, vc = jax.random.normal(jax.random.PRNGKey(99),
                               (2, nb, h, bs, d), jnp.float32)
    scales = {}
    if layout.startswith("int8"):
        kc, ksc = quantize_kv_rows(kc)
        vc, vsc = quantize_kv_rows(vc)
        scales = dict(k_scale=ksc, v_scale=vsc)
    if layout in ("packed", "int8_packed"):
        kc, vc = _pack_cache(kc), _pack_cache(vc)
    bt = np.full((b, mp), DUMP_BLOCK, np.int32)
    pool = np.random.RandomState(seed).permutation(np.arange(1, nb))
    nxt = 0
    for i, n in enumerate(lens):
        pages = -(-n // bs)
        bt[i, :pages] = pool[nxt:nxt + pages]
        nxt += pages
    return (q, kc, vc, jnp.asarray(bt),
            jnp.asarray(np.asarray(lens, np.int32)), scales)


def _assert_close(got, want, dtype):
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# kernel parity (the APX402 anchor)
# ---------------------------------------------------------------------------

class TestFlashDecodeParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_parity_unpacked(self, dtype):
        q, kc, vc, bt, sl, _, _ = make_paged_case(dtype=dtype)
        got = flash_decode(q, kc, vc, bt, sl)
        want = paged_attention_reference(q, kc, vc, bt, sl)
        assert got.dtype == dtype
        _assert_close(got, want, dtype)

    def test_parity_packed_d64(self):
        assert use_decode_head_packing(4, 64)
        q, kc, vc, bt, sl, _, _ = make_paged_case(
            b=3, h=4, d=64, nb=8, bs=4, mp=3, packed=True)
        got = flash_decode(q, kc, vc, bt, sl)
        want = paged_attention_reference(q, kc, vc, bt, sl)
        _assert_close(got, want, jnp.float32)

    def test_packed_matches_unpacked_math(self):
        # same dense cache through both layouts -> same attention
        q, kd, vd, bt, sl, _, _ = make_paged_case(b=3, h=4, d=64,
                                                  nb=8, bs=4, mp=3)
        unpacked = flash_decode(q, kd, vd, bt, sl)
        packed = flash_decode(q, _pack_cache(kd), _pack_cache(vd),
                              bt, sl)
        _assert_close(packed, unpacked, jnp.float32)

    def test_parity_int8_unpacked(self):
        q, kc, vc, bt, sl, ksc, vsc = make_paged_case(int8=True)
        got = flash_decode(q, kc, vc, bt, sl, k_scale=ksc,
                           v_scale=vsc)
        want = paged_attention_reference(q, kc, vc, bt, sl,
                                         k_scale=ksc, v_scale=vsc)
        _assert_close(got, want, jnp.float32)

    def test_parity_int8_packed(self):
        q, kd, vd, bt, sl, _, _ = make_paged_case(b=3, h=4, d=64,
                                                  nb=8, bs=4, mp=3)
        kq, ksc = quantize_kv_rows(kd)
        vq, vsc = quantize_kv_rows(vd)
        got = flash_decode(q, _pack_cache(kq), _pack_cache(vq), bt,
                           sl, k_scale=ksc, v_scale=vsc)
        want = paged_attention_reference(
            q, _pack_cache(kq), _pack_cache(vq), bt, sl, k_scale=ksc,
            v_scale=vsc)
        _assert_close(got, want, jnp.float32)

    def test_int8_tracks_f32_attention(self):
        # weight-only int8 is an approximation of the float cache —
        # per-row scales keep it within quantization noise
        q, kd, vd, bt, sl, _, _ = make_paged_case(seed=3)
        exact = flash_decode(q, kd, vd, bt, sl)
        kq, ksc = quantize_kv_rows(kd)
        vq, vsc = quantize_kv_rows(vd)
        quant = flash_decode(q, kq, vq, bt, sl, k_scale=ksc,
                             v_scale=vsc)
        np.testing.assert_allclose(np.asarray(quant), np.asarray(exact),
                                   rtol=0.2, atol=0.1)

    def test_inactive_row_is_exactly_zero(self):
        q, kc, vc, bt, sl, _, _ = make_paged_case()
        assert int(sl[0]) == 0
        out = flash_decode(q, kc, vc, bt, sl)
        assert np.all(np.asarray(out)[0] == 0.0)

    def test_mask_ignores_garbage_past_seq_len(self):
        # poison every position >= seq_len (including whole dump-padded
        # pages) with huge values: masked positions must not leak
        q, kc, vc, bt, sl, _, _ = make_paged_case(seed=5)
        clean = flash_decode(q, kc, vc, bt, sl)
        poisoned = np.asarray(kc).copy()
        poisoned[DUMP_BLOCK] = 1e9
        i = 1                       # the straddling row
        last_page = int(sl[i] - 1) // kc.shape[2]
        blk = int(bt[i, last_page])
        off = int(sl[i]) % kc.shape[2]
        if off:
            poisoned[blk, :, off:, :] = 1e9
        got = flash_decode(q, jnp.asarray(poisoned), vc, bt, sl)
        _assert_close(got, clean, jnp.float32)

    # --- the page-a-step grid (every head group of a page in one
    # program): lengths at each edge of a page, and rung invariance ---

    @pytest.mark.parametrize("layout", ["unpacked", "packed", "int8",
                                        "int8_packed"])
    @pytest.mark.parametrize("length", ["0", "1", "bs-1", "bs", "bs+1",
                                        "full"])
    def test_ragged_lengths(self, layout, length):
        bs, mp = 8, 4
        n = {"0": 0, "1": 1, "bs-1": bs - 1, "bs": bs, "bs+1": bs + 1,
             "full": mp * bs}[length]
        case = make_ragged_case([n, mp * bs, 3], layout, bs=bs, mp=mp)
        q, kc, vc, bt, sl, scales = case
        got = flash_decode(q, kc, vc, bt, sl, **scales)
        want = paged_attention_reference(q, kc, vc, bt, sl, **scales)
        _assert_close(got, want, jnp.float32)
        if n == 0:
            assert np.all(np.asarray(got)[0] == 0.0)

    @pytest.mark.parametrize("layout", ["unpacked", "packed", "int8",
                                        "int8_packed"])
    def test_heads_split_by_vmem_budget(self, layout, monkeypatch):
        # a step whose working set passes the budget takes a divisor of
        # the head groups (eight: a sublane tile of queries), same
        # attention
        from apex_tpu.ops import flash_decode as fd
        case = make_ragged_case([0, 48, 13], layout, h=32, bs=8, mp=6)
        q, kc, vc, bt, sl, scales = case
        hk, dk = kc.shape[1], kc.shape[3]
        whole = flash_decode(q, kc, vc, bt, sl, **scales)
        monkeypatch.setattr(fd, "_STEP_VMEM_BYTES",
                            fd._step_vmem_bytes(8, 1, 8, dk))
        assert fd._heads_per_step(hk, 1, 8, dk) == 8 < hk
        # the drivers are jitted on shapes: a budget changed under
        # them needs a fresh trace
        jax.clear_caches()
        split = flash_decode(q, kc, vc, bt, sl, **scales)
        jax.clear_caches()
        _assert_close(split, whole, jnp.float32)

    @pytest.mark.parametrize("hk,t,bs,dk,want", [
        (8, 1, 16, 128, 8),       # the cells' decode: every head group
        (8, 4, 16, 128, 8),       # speculative verify
        (8, 256, 16, 128, 4),     # a 256-token chunk of the cells' cache
        (8, 1024, 16, 128, 1),    # the 1,024 rung: one head group
        (16, 1, 16, 128, 16),     # unpacked d=128, 16 heads
        (64, 1, 64, 128, 16),     # 64 heads in 64-token blocks: splits
        (24, 1, 128, 128, 8),     # 12 fits but is no sublane tile
        (20, 1, 128, 128, 20),    # no tile divides 20: whole, over budget
    ])
    def test_heads_per_step(self, hk, t, bs, dk, want):
        from apex_tpu.ops.flash_decode import _heads_per_step
        hg = _heads_per_step(hk, t, bs, dk)
        assert hg == want
        assert hg == hk or hg * t % 8 == 0

    @pytest.mark.parametrize("layout", ["unpacked", "packed", "int8",
                                        "int8_packed"])
    def test_rung_invariance(self, layout):
        # one request, alone on the smallest rungs and as row 0 / row 31
        # of a 32 x 64-page bucket among other rows: bitwise the same
        # attention (gpt_decode_step's docstring promises a token
        # stream invariant to bucket shape)
        bs, length = 8, 16 * 8 - 3
        small = make_ragged_case([length], layout, bs=bs, mp=16, nb=600)
        q1, kc, vc, bt1, sl1, scales = small
        alone = np.asarray(flash_decode(q1, kc, vc, bt1, sl1, **scales))
        others = [5, 64 * bs, 0, 17] * 8
        for row in (0, 31):
            lens = others[:31]
            lens.insert(row, length)
            q, _, _, bt, sl, _ = make_ragged_case(
                lens, layout, bs=bs, mp=64, nb=600, seed=row + 1)
            q = q.at[row].set(q1[0])
            bt = bt.at[row].set(DUMP_BLOCK).at[row, :16].set(bt1[0])
            # the other rows' tables may name this request's blocks
            # too: a row reads its pages, whoever else reads them
            got = np.asarray(flash_decode(q, kc, vc, bt, sl, **scales))
            assert np.array_equal(got[row], alone[0]), (layout, row)

    def test_pack_unpack_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 64))
        assert jnp.array_equal(
            unpack_decode_heads(pack_decode_heads(x)), x)

    def test_layout_mismatch_raises(self):
        q, kc, vc, bt, sl, _, _ = make_paged_case()
        with pytest.raises(ValueError, match="head layout"):
            flash_decode(q, kc[:, :, :, :16], vc[:, :, :, :16], bt, sl)
        with pytest.raises(ValueError, match="both k_scale"):
            flash_decode(q, kc, vc, bt, sl,
                         k_scale=jnp.zeros(kc.shape[:2] + kc.shape[2:3]))

    def test_bad_scale_shapes_raise(self):
        # BOTH scales are validated — a misshapen v_scale must raise,
        # not silently dequantize v with garbage factors
        q, kc, vc, bt, sl, _, _ = make_paged_case(int8=True)
        nb, h, bs, _ = kc.shape
        good = jnp.ones((nb, h, bs), jnp.float32)
        with pytest.raises(ValueError, match="k_scale shape"):
            flash_decode(q, kc, vc, bt, sl,
                         k_scale=jnp.ones((nb, h, bs + 1)), v_scale=good)
        with pytest.raises(ValueError, match="v_scale shape"):
            flash_decode(q, kc, vc, bt, sl,
                         k_scale=good, v_scale=jnp.ones((nb, h, bs + 1)))

    def test_packing_escape_hatch(self):
        assert use_decode_head_packing(4, 64)
        set_head_packing(False)
        try:
            assert not use_decode_head_packing(4, 64)
        finally:
            set_head_packing(True)
        assert not use_decode_head_packing(3, 64)   # odd heads
        assert not use_decode_head_packing(4, 32)   # d != 64


def make_multi_case(b=3, t=3, h=2, d=32, nb=10, bs=8, mp=3, *, seed=0,
                    dtype=jnp.float32):
    """Random (b, t) chunk queries + paged cache: row 0 inactive,
    row 1 straddling mid-block, row 2 exactly filling its pages."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, t, h, d), dtype)
    kd = jax.random.normal(ks[1], (nb, h, bs, d), dtype)
    vd = jax.random.normal(ks[2], (nb, h, bs, d), dtype)
    bt = np.full((b, mp), DUMP_BLOCK, np.int32)
    sl = np.zeros(b, np.int32)
    sl[1] = mp * bs - bs // 2 - 1
    bt[1, :2] = [3, 4]
    sl[2] = mp * bs
    bt[2] = [5, 6, 7]
    return q, kd, vd, jnp.asarray(bt), jnp.asarray(sl)


class TestFlashDecodeMultiParity:
    """The APX402 anchor for the multi-token (speculative-verify /
    chunked-prefill) kernel: :func:`flash_decode_multi` vs
    :func:`paged_attention_multi_reference`."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_parity_unpacked(self, dtype):
        q, kd, vd, bt, sl = make_multi_case(dtype=dtype)
        got = flash_decode_multi(q, kd, vd, bt, sl)
        want = paged_attention_multi_reference(q, kd, vd, bt, sl)
        assert got.dtype == dtype
        _assert_close(got, want, dtype)

    def test_parity_packed_d64(self):
        q, kd, vd, bt, sl = make_multi_case(h=4, d=64, bs=4)
        got = flash_decode_multi(q, _pack_cache(kd), _pack_cache(vd),
                                 bt, sl)
        want = paged_attention_multi_reference(
            q, _pack_cache(kd), _pack_cache(vd), bt, sl)
        _assert_close(got, want, jnp.float32)

    def test_parity_int8(self):
        q, kd, vd, bt, sl = make_multi_case(seed=3)
        kq, ksc = quantize_kv_rows(kd)
        vq, vsc = quantize_kv_rows(vd)
        got = flash_decode_multi(q, kq, vq, bt, sl, k_scale=ksc,
                                 v_scale=vsc)
        want = paged_attention_multi_reference(
            q, kq, vq, bt, sl, k_scale=ksc, v_scale=vsc)
        _assert_close(got, want, jnp.float32)

    @pytest.mark.parametrize("layout", ["unpacked", "packed", "int8",
                                        "int8_packed"])
    @pytest.mark.parametrize("hg", [1, 2])
    def test_chunk_splits_heads_by_vmem_budget(self, layout, hg,
                                               monkeypatch):
        # a long chunk's carries pass the budget: a step takes fewer
        # head groups (one at the cells' 1,024 rung), same attention
        from apex_tpu.ops import flash_decode as fd
        _, kc, vc, bt, sl, scales = make_ragged_case(
            [0, 45, 13], layout, h=8, bs=8, mp=6)
        t, (hk, dk) = 8, (kc.shape[1], kc.shape[3])
        q = jax.random.normal(jax.random.PRNGKey(7), (3, t, 8, 64))
        want = paged_attention_multi_reference(q, kc, vc, bt, sl,
                                               **scales)
        monkeypatch.setattr(fd, "_STEP_VMEM_BYTES",
                            fd._step_vmem_bytes(hg, t, 8, dk))
        assert fd._heads_per_step(hk, t, 8, dk) == hg < hk
        jax.clear_caches()
        got = flash_decode_multi(q, kc, vc, bt, sl, **scales)
        jax.clear_caches()
        _assert_close(got, want, jnp.float32)

    def test_t1_matches_single_token_decode(self):
        # the degenerate chunk is exactly the decode kernel's math
        q, kc, vc, bt, sl, _, _ = make_paged_case()
        one = flash_decode(q, kc, vc, bt, sl)
        multi = flash_decode_multi(q[:, None], kc, vc, bt, sl)[:, 0]
        _assert_close(multi, one, jnp.float32)

    def test_inactive_and_padding_rows_zero(self):
        # inactive sequences (sl=0) and front-padding rows (negative
        # chunk positions, sl < t) both emit exactly 0
        q, kd, vd, bt, sl = make_multi_case(t=5)
        out = np.asarray(flash_decode_multi(q, kd, vd, bt, sl))
        assert np.all(out[0] == 0.0)            # inactive row
        short = jnp.asarray(np.asarray([2, 2, 2], np.int32))
        out2 = np.asarray(flash_decode_multi(q, kd, vd, bt, short))
        assert np.all(out2[:, :3] == 0.0)       # positions -3..-1
        want = paged_attention_multi_reference(q, kd, vd, bt, short)
        _assert_close(out2, want, jnp.float32)

    def test_per_row_causality(self):
        # poisoning position p must change only rows whose causal
        # span reaches p: row r attends pos <= sl - t + r
        q, kd, vd, bt, sl = make_multi_case(seed=5)
        clean = np.asarray(flash_decode_multi(q, kd, vd, bt, sl))
        i, bs = 2, kd.shape[2]
        last = int(sl[i]) - 1                   # newest position
        blk, off = int(bt[i, last // bs]), last % bs
        poisoned = np.asarray(kd).copy()
        poisoned[blk, :, off, :] += 3.0
        got = np.asarray(flash_decode_multi(
            q, jnp.asarray(poisoned), vd, bt, sl))
        t = q.shape[1]
        # only the final row of row-i's chunk sees the newest slot
        _assert_close(got[i, :t - 1], clean[i, :t - 1], jnp.float32)
        assert not np.allclose(got[i, t - 1], clean[i, t - 1])


# ---------------------------------------------------------------------------
# KV paging invariants
# ---------------------------------------------------------------------------


# --- the page write's cases: per row (first position, tokens written,
# where the chunk's unwritten slots are, the row's block table) ---------

_PAGE_WRITE_CASES = {
    # one token a row (a decode tick), the third row inactive
    "decode": (1, [(6, 1, None, [2, 5]), (3, 1, None, [7]),
                   (0, 0, None, [])]),
    # several tokens of a chunk on one page
    "extend_same_page": (3, [(4, 3, None, [2, 5]), (0, 3, None, [7]),
                             (0, 0, "front", [])]),
    # a chunk across a page boundary, and one ending a page exactly
    "extend_crosses_page": (4, [(2, 4, None, [2, 5]), (4, 4, None, [7, 8]),
                                (0, 0, "front", [])]),
    # a chunk over three pages, and one page-aligned over two
    "extend_three_pages": (6, [(3, 6, None, [9, 3, 4]),
                               (0, 6, None, [6, 1]),
                               (0, 0, "front", [])]),
    # front padding: the written tokens are the chunk's last
    "extend_front_padded": (4, [(5, 2, "front", [2, 5]),
                                (3, 3, "front", [7, 8]),
                                (0, 1, "front", [4])]),
    # a speculative row near its budget: the overshoot goes to the dump
    "extend_back_padded": (4, [(3, 2, "back", [2, 5]),
                               (6, 1, "back", [7, 8]),
                               (0, 4, None, [4])]),
}


def _page_write_slots(case, bs):
    """(blocks, offsets) as the engine builds them: (b,) for one token
    a row, else (b, t); unwritten slots at the dump page, offset 0."""
    t, rows = case
    blocks = np.full((len(rows), t), DUMP_BLOCK, np.int32)
    offsets = np.zeros((len(rows), t), np.int32)
    for i, (first, n, pad, table) in enumerate(rows):
        at = t - n if pad == "front" else 0
        for j in range(n):
            blocks[i, at + j] = table[(first + j) // bs]
            offsets[i, at + j] = (first + j) % bs
    if t == 1:
        return blocks[:, 0], offsets[:, 0]
    return blocks, offsets


def _row_by_row_write(cache, cfg, layer, k, v, blocks, offsets):
    """The plain reference of :func:`write_token_kv`: every token's
    storage row set alone, ``arr[block, :, offset] = row``, in numpy;
    dump-page tokens left out.  Returns numpy leaves."""
    out = [None if leaves is None else [np.array(a) for a in leaves]
           for leaves in cache]
    h, d = cfg.num_heads, cfg.head_dim
    blocks, offsets = blocks.reshape(-1), offsets.reshape(-1)
    for new, arr, scales in ((k, out[0], out[2]), (v, out[1], out[3])):
        rows = np.asarray(new, np.float32).reshape(-1, h, d)
        for row, blk, off in zip(rows, blocks, offsets):
            if blk == DUMP_BLOCK:
                continue
            if cfg.quantized:
                q, scale = quantize_kv_rows(jnp.asarray(row))
                scales[layer][blk, :, off] = np.asarray(scale)
                row = np.asarray(q)
            if cfg.packed:
                row = row.reshape(h // 2, 2 * d)
            arr[layer][blk, :, off] = row.astype(arr[layer].dtype)
    return out


def _cfg(**kw):
    base = dict(num_layers=1, num_heads=2, head_dim=8, num_blocks=6,
                block_size=4)
    base.update(kw)
    return KVCacheConfig(**base)


class TestKVCacheManager:
    def test_append_past_block_boundary(self):
        mgr = KVCacheManager(_cfg())
        blocks = mgr.alloc("r", 4)          # exactly one full page
        assert len(blocks) == 1 and mgr.seq_len("r") == 4
        blk, off = mgr.append("r")          # token 5 opens page 2
        assert blk != blocks[0] and off == 0
        assert mgr.num_pages("r") == 2 and mgr.seq_len("r") == 5
        blk2, off2 = mgr.append("r")
        assert blk2 == blk and off2 == 1    # stays on the new page

    def test_evict_readmit_reuses_blocks_bitwise(self):
        mgr = KVCacheManager(_cfg())
        first = mgr.alloc("a", 7)           # two pages
        assert mgr.free("a") == first
        again = mgr.alloc("b", 7)
        assert again == first               # LIFO + reversed free
        assert mgr.free_blocks == _cfg().usable_blocks - 2

    def test_pool_exhaustion_and_admission_control(self):
        cfg = _cfg(num_blocks=3)            # 2 usable
        mgr = KVCacheManager(cfg)
        assert mgr.can_admit(4, 4)                      # 2 blocks
        assert not mgr.can_admit(8, 1)                  # needs 3
        # blocks the pool owes in-flight requests count against the
        # free list — the engine's reservation admission delegates here
        assert not mgr.can_admit(4, 4, reserved_blocks=1)
        mgr.alloc("a", 8)                   # both usable blocks
        with pytest.raises(CachePoolExhausted):
            mgr.alloc("b", 1)
        # crossing a block edge with the pool empty is the raced case
        mgr2 = KVCacheManager(cfg)
        mgr2.alloc("a", 4)
        mgr2.alloc("b", 4)
        with pytest.raises(CachePoolExhausted):
            mgr2.append("a")

    def test_block_table_padding_and_overflow(self):
        mgr = KVCacheManager(_cfg())
        mgr.alloc("r", 5)                   # two pages
        bt = mgr.block_table("r", 4)
        assert bt.dtype == np.int32 and list(bt[2:]) == [DUMP_BLOCK] * 2
        assert list(bt[:2]) == mgr.blocks("r")
        with pytest.raises(ValueError, match="max_pages"):
            mgr.block_table("r", 1)

    def test_double_alloc_and_bad_args(self):
        mgr = KVCacheManager(_cfg())
        mgr.alloc("r", 1)
        with pytest.raises(ValueError, match="already"):
            mgr.alloc("r", 1)
        with pytest.raises(ValueError, match="length"):
            mgr.alloc("s", 0)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="dump"):
            _cfg(num_blocks=1)
        with pytest.raises(ValueError, match="kv_dtype"):
            _cfg(kv_dtype="fp8")


class TestCacheWrites:
    @pytest.mark.parametrize("kv_dtype", ["model", "bf16", "int8"])
    def test_token_write_readback(self, kv_dtype):
        cfg = _cfg(kv_dtype=kv_dtype)
        cache = init_cache(cfg)
        k = jax.random.normal(jax.random.PRNGKey(0),
                              (2, cfg.num_heads, cfg.head_dim))
        cache = write_token_kv(
            cache, cfg, 0, k, k * 2.0, plan_page_write(
                jnp.asarray([1, 3]), jnp.asarray([2, 0]), cfg.block_size))
        kc, vc, ks, vs = cache.layer(0)
        got_k = paged_attention_reference(
            jnp.ones((2, cfg.num_heads, cfg.head_dim)), kc, vc,
            jnp.asarray([[1], [3]]), jnp.asarray([0, 0]), k_scale=ks,
            v_scale=vs)
        # direct slot readback (dequantized via the twin's helper)
        from apex_tpu.ops.flash_decode import dequantize_kv

        kd = dequantize_kv(kc, ks)
        if cfg.packed:
            kd = unpack_decode_heads(
                kd.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
        tol = {"model": 0, "bf16": 2e-2, "int8": 5e-2}[kv_dtype]
        np.testing.assert_allclose(np.asarray(kd[1, :, 2, :]),
                                   np.asarray(k[0], np.float32),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(kd[3, :, 0, :]),
                                   np.asarray(k[1], np.float32),
                                   rtol=tol, atol=tol)
        assert got_k.shape == (2, cfg.num_heads, cfg.head_dim)

    def test_prefill_write_matches_token_writes(self):
        # one whole-prompt scatter == the same tokens written one by one
        cfg = _cfg()
        n, bs = 6, cfg.block_size
        k = jax.random.normal(jax.random.PRNGKey(1),
                              (2 * bs, cfg.num_heads, cfg.head_dim))
        v = jax.random.normal(jax.random.PRNGKey(2), k.shape)
        blocks = jnp.asarray([2, 4])
        whole = write_prefill_kv(init_cache(cfg), cfg, 0, k, v, blocks)
        step = init_cache(cfg)
        for t in range(n):
            step = write_token_kv(
                step, cfg, 0, k[t][None], v[t][None], plan_page_write(
                    jnp.asarray([int(blocks[t // bs])]),
                    jnp.asarray([t % bs]), bs))
        got = np.asarray(whole.k[0])
        want = np.asarray(step.k[0])
        # rows past n were zero-padded in the whole-prompt write
        np.testing.assert_array_equal(got[2], want[2])
        np.testing.assert_array_equal(got[4, :, :n - bs],
                                      want[4, :, :n - bs])

    def test_cache_is_one_array_a_layer(self):
        cfg = _cfg(num_layers=3, kv_dtype="int8")
        cache = init_cache(cfg)
        assert len(cache.k) == len(cache.v) == 3
        assert len(cache.k_scale) == len(cache.v_scale) == 3
        assert cfg.kv_shape == (6, 2, 4, 8) and cfg.scale_shape == (6, 2, 4)
        assert all(a.shape == cfg.kv_shape and a.dtype == jnp.int8
                   for a in cache.k + cache.v)
        assert all(a.shape == cfg.scale_shape
                   for a in cache.k_scale + cache.v_scale)
        # donated every step: no two leaves may share a buffer
        leaves = jax.tree.leaves(cache)
        assert len({a.unsafe_buffer_pointer() for a in leaves}) == 12
        assert cfg.cache_nbytes() == sum(a.nbytes for a in leaves)
        assert init_cache(_cfg()).k_scale is None

    @pytest.mark.parametrize("packed", [False, True],
                             ids=["unpacked", "packed"])
    @pytest.mark.parametrize("kv_dtype", ["model", "bf16", "int8"])
    @pytest.mark.parametrize("case", list(_PAGE_WRITE_CASES))
    def test_page_write_matches_row_writes(self, case, kv_dtype, packed):
        # the page-granular write (gather pages, put rows in, scatter
        # pages back) against a plain row-by-row numpy write
        cfg = _cfg(num_layers=2, num_blocks=10, kv_dtype=kv_dtype,
                   head_dim=64 if packed else 8)
        assert cfg.packed == packed
        blocks, offsets = _page_write_slots(_PAGE_WRITE_CASES[case],
                                            cfg.block_size)
        rng = np.random.RandomState(7)

        def fill(shape, dtype):
            x = rng.randint(-100, 100, shape) if dtype == jnp.int8 \
                else rng.randn(*shape)
            return jnp.asarray(x).astype(dtype)

        cache = init_cache(cfg)
        cache = jax.tree.map(lambda a: fill(a.shape, a.dtype), cache)
        new = blocks.shape + (cfg.num_heads, cfg.head_dim)
        k = jnp.asarray(rng.randn(*new), jnp.float32)
        v = jnp.asarray(rng.randn(*new), jnp.float32)
        got = write_token_kv(cache, cfg, 1, k, v, plan_page_write(
            jnp.asarray(blocks), jnp.asarray(offsets), cfg.block_size))
        want = _row_by_row_write(cache, cfg, 1, k, v, blocks, offsets)
        for name, g, w, before in zip(got._fields, got, want, cache):
            if g is None:
                assert w is None
                continue
            # only layer 1's leaves change; layer 0's pass through
            assert g[0] is before[0], name
            # the dump page holds whatever: it is never read unmasked
            np.testing.assert_array_equal(
                np.asarray(g[1])[1:], w[1][1:], err_msg=name)
    def test_quantize_rows_roundtrip(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 3, 16)) * 5.0
        q, s = quantize_kv_rows(x)
        assert q.dtype == jnp.int8 and s.shape == (4, 3)
        back = np.asarray(q, np.float32) * np.asarray(s)[..., None]
        np.testing.assert_allclose(back, np.asarray(x), atol=np.max(
            np.abs(np.asarray(x))) / 127.0 * 1.01)

    def test_quantize_weight_zero_channel_roundtrip(self):
        # the weight-quantizer analogue of the KV scale floor
        # (ISSUE-16): an all-zero output channel must round-trip to
        # exactly 0.0 with a finite floored scale, never 0/0 = NaN
        from apex_tpu.ops.quant_matmul import (dequantize_weight,
                                               quantize_weight)
        w = jnp.zeros((16, 4), jnp.float32).at[:, 1].set(2.0)
        wq, sc = quantize_weight(w)
        assert np.all(np.isfinite(np.asarray(sc))) and np.all(
            np.asarray(sc) > 0.0)
        deq = np.asarray(dequantize_weight(wq, sc))
        assert np.all(deq[:, 0] == 0.0)
        assert np.all(deq[:, 2:] == 0.0)
        np.testing.assert_allclose(deq[:, 1], 2.0)


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------

class TestBucketLadder:
    def test_pick_rounds_up(self):
        lad = BucketLadder(batch=(1, 2, 4), pages=(2, 8))
        assert lad.pick_batch(1) == 1
        assert lad.pick_batch(3) == 4
        assert lad.pick_pages(3) == 8
        with pytest.raises(ValueError, match="exceeds the ladder"):
            lad.pick_batch(5)

    def test_from_flags_and_validation(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_SERVE_BATCH_BUCKETS", "4,1,2")
        monkeypatch.setenv("APEX_TPU_SERVE_PAGE_BUCKETS", "8")
        lad = BucketLadder.from_flags()
        assert lad.batch == (1, 2, 4) and lad.pages == (8,)
        monkeypatch.setenv("APEX_TPU_SERVE_BATCH_BUCKETS", "0,2")
        with pytest.raises(ValueError, match="positive"):
            BucketLadder.from_flags()


# ---------------------------------------------------------------------------
# serving model + engine
# ---------------------------------------------------------------------------

def _tiny_model(vocab=32, hidden=16, heads=2, layers=2, max_seq=32,
                seed=0):
    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_sequence_length=max_seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=jnp.float32)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, *, ladder, num_blocks=16, block_size=4,
            kv_dtype="model", decode_attention="reference",
            autoresume=None, clock=None):
    cfg = ServingModelConfig.from_model(
        model, prefill_flash=False, decode_attention=decode_attention)
    weights = extract_serving_weights(params, cfg.num_layers)
    cache_cfg = default_cache_config(cfg, num_blocks=num_blocks,
                                     block_size=block_size,
                                     kv_dtype=kv_dtype)
    extra = {} if clock is None else {"clock": clock}
    return ServingEngine(weights, cfg, cache_cfg, ladder=ladder,
                         autoresume=autoresume, **extra)


def _greedy_reference(model, params, prompt, new_tokens):
    """Whole-sequence teacher-forced argmax loop — the no-cache oracle
    the serving stack must reproduce token for token."""
    toks = list(prompt)
    for _ in range(new_tokens):
        logits = model.apply({"params": params},
                             jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


class TestServingModelParity:
    def test_prefill_decode_matches_whole_sequence_model(self):
        # end-to-end: paged prefill + per-token decode == teacher-forced
        # GPTModel.apply greedy generation, bitwise on token ids
        model, params = _tiny_model()
        lad = BucketLadder(batch=(2,), pages=(3,))
        eng = _engine(model, params, ladder=lad)
        prompts = [[3, 7, 1], [11, 2, 9, 4, 5]]
        new = 4
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=f"r{i}", prompt=p,
                               max_new_tokens=new))
        eng.run()
        assert len(eng.done) == 2
        by_rid = {q.rid: q.out_tokens for q in eng.done}
        for i, p in enumerate(prompts):
            want = _greedy_reference(model, params, p, new)
            assert by_rid[f"r{i}"] == want, (i, by_rid[f"r{i}"], want)

    def test_decode_kernel_path_matches_reference_path(self):
        # the same trace through the Pallas kernel and the dense twin
        model, params = _tiny_model(hidden=128, heads=2)  # d=64 packed
        lad = BucketLadder(batch=(2,), pages=(2,))
        prompts = [[5, 1], [9, 3, 2]]
        streams = {}
        for mode in ("kernel", "reference"):
            eng = _engine(model, params, ladder=lad,
                          decode_attention=mode)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=f"r{i}", prompt=p,
                                   max_new_tokens=3))
            eng.run()
            streams[mode] = {q.rid: q.out_tokens for q in eng.done}
        assert streams["kernel"] == streams["reference"]

    def test_bad_requests_rejected(self):
        model, params = _tiny_model()
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2,), pages=(2,)))
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(Request(rid="e", prompt=[], max_new_tokens=1))
        with pytest.raises(ValueError, match="span"):
            eng.submit(Request(rid="big", prompt=[1] * 8,
                               max_new_tokens=4))   # 12 > 2*4
        # non-positive budgets undercount the reservation admission
        # math (prompt + max_new) — rejected at the door
        for bad in (0, -9):
            with pytest.raises(ValueError, match="max_new_tokens"):
                eng.submit(Request(rid="z", prompt=[1, 2, 3],
                                   max_new_tokens=bad))


# Committed divergence bound for the Q8 tier (ISSUE-16): int8
# weight-only quantization may flip at most this fraction of greedy
# tokens vs the float engine on the smoke GPT (measured 0/24 across
# seeds; the bound leaves quantization-noise headroom, it is not a
# target).
Q8_GREEDY_DIVERGENCE_BOUND = 0.10


class TestQ8Serving:
    def test_q8_greedy_tracks_float_within_committed_bound(self):
        from apex_tpu.ops.quant_matmul import quantize_weights
        model, params = _tiny_model(vocab=64, hidden=64, heads=2)
        cfg = ServingModelConfig.from_model(
            model, prefill_flash=False, decode_attention="reference")
        weights = extract_serving_weights(params, cfg.num_layers)
        cache_cfg = default_cache_config(cfg, num_blocks=16,
                                         block_size=4)
        lad = BucketLadder(batch=(2,), pages=(3,))
        prompts = [[3, 7, 1], [11, 2, 9, 4, 5], [1, 2], [6, 6, 6, 6]]
        new = 6
        outs = {}
        for tag, w in (("float", weights),
                       ("q8", quantize_weights(weights))):
            eng = ServingEngine(w, cfg, cache_cfg, ladder=lad)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=f"r{i}", prompt=p,
                                   max_new_tokens=new))
            eng.run()
            assert len(eng.done) == len(prompts)
            outs[tag] = {q.rid: q.out_tokens for q in eng.done}
        total = sum(len(v) for v in outs["float"].values())
        diverged = sum(a != b for rid in outs["float"]
                       for a, b in zip(outs["float"][rid],
                                       outs["q8"][rid]))
        assert diverged / total <= Q8_GREEDY_DIVERGENCE_BOUND, (
            diverged, total)

    def test_q8_swap_back_and_forth(self):
        # bf16<->int8 requantization swaps both directions; the
        # second direction restores the original treedef bitwise path
        from apex_tpu.ops.quant_matmul import (is_quantized_weights,
                                               quantize_weights)
        model, params = _tiny_model()
        lad = BucketLadder(batch=(2,), pages=(3,))
        eng = _engine(model, params, ladder=lad)
        weights = eng.weights
        eng.swap_weights(quantize_weights(weights))
        assert is_quantized_weights(eng.weights)
        eng.swap_weights(weights)
        assert not is_quantized_weights(eng.weights)
        eng.submit(Request(rid="r", prompt=[3, 1, 4],
                           max_new_tokens=3))
        eng.run()
        assert len(eng.done) == 1


class TestContinuousBatching:
    def _serve(self, model, params, prompts, *, staggered,
               new_tokens=4, **kw):
        eng = _engine(model, params, **kw)
        reqs = [Request(rid=f"r{i}", prompt=list(p),
                        max_new_tokens=new_tokens)
                for i, p in enumerate(prompts)]
        if staggered:
            eng.submit(reqs[0])
            pending = reqs[1:]

            def drip(step):
                if pending:
                    eng.submit(pending.pop(0))

            summary = eng.run(before_tick=drip)
            while pending:
                eng.submit(pending.pop(0))
                summary = eng.run()
        else:
            for r in reqs:
                eng.submit(r)
            summary = eng.run()
        return eng, summary

    def test_determinism_across_admission_interleave(self):
        # same request trace => same tokens, whether everything is
        # admitted up front or admissions drip between decode steps
        model, params = _tiny_model()
        prompts = [[2, 5], [7, 1, 3, 8], [4]]
        kw = dict(ladder=BucketLadder(batch=(1, 2, 4), pages=(2,)),
                  num_blocks=16)
        eng_a, _ = self._serve(model, params, prompts, staggered=False,
                               **kw)
        eng_b, _ = self._serve(model, params, prompts, staggered=True,
                               **kw)
        tok_a = {q.rid: q.out_tokens for q in eng_a.done}
        tok_b = {q.rid: q.out_tokens for q in eng_b.done}
        assert tok_a == tok_b

    def test_determinism_across_bucket_shapes(self):
        # a fatter batch bucket pads with inactive rows; the ladder
        # choice must not change any request's tokens
        model, params = _tiny_model()
        prompts = [[2, 5], [7, 1, 3]]
        tok = {}
        for name, lad in (("tight", BucketLadder(batch=(2,),
                                                 pages=(2,))),
                          ("padded", BucketLadder(batch=(8,),
                                                  pages=(2, 4)))):
            eng, _ = self._serve(model, params, prompts,
                                 staggered=False, ladder=lad,
                                 num_blocks=40)
            tok[name] = {q.rid: q.out_tokens for q in eng.done}
        assert tok["tight"] == tok["padded"]

    def test_resumed_run_reports_lifetime_wall(self):
        # a paused-and-resumed serve (max_steps, or bench's staggered
        # tail admissions) must report lifetime tokens over lifetime
        # in-run wall — not lifetime tokens over only the resumed
        # tail's wall, which inflates tokens/s
        model, params = _tiny_model()
        prompts = [[2, 5], [7, 1, 3]]
        lad = BucketLadder(batch=(2,), pages=(2,))

        def fake_clock():
            fake_clock.t += 1.0
            return fake_clock.t

        summaries = {}
        for name, pause in (("straight", None), ("paused", 2)):
            fake_clock.t = 0.0
            eng = _engine(model, params, ladder=lad, clock=fake_clock)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=f"r{i}", prompt=list(p),
                                   max_new_tokens=4))
            s = eng.run(max_steps=pause)
            while eng.queue or eng.active:
                s = eng.run()
            summaries[name] = s
        a, b = summaries["straight"], summaries["paused"]
        assert b.tokens_generated == a.tokens_generated
        # the paused serve spends strictly MORE clock inside run()
        # (one extra start/stop pair), never less — so its reported
        # rate can only come out at or below the uninterrupted one
        assert b.wall_s >= a.wall_s
        assert b.tokens_per_sec <= a.tokens_per_sec
        assert b.tokens_per_sec == pytest.approx(
            b.tokens_generated / b.wall_s, abs=0.01)

    def test_decode_rate_excludes_prefill_wall(self):
        # decode_tokens_per_sec divides decode-tick tokens by
        # decode-tick wall only — prefill time (identical across
        # kernel/naive engines) must not dilute the bench ratio
        model, params = _tiny_model()
        lad = BucketLadder(batch=(2,), pages=(2,))

        def fake_clock():
            fake_clock.t += 1.0
            return fake_clock.t
        fake_clock.t = 0.0

        eng = _engine(model, params, ladder=lad, clock=fake_clock)
        for i, p in enumerate([[2, 5], [7, 1, 3]]):
            eng.submit(Request(rid=f"r{i}", prompt=p,
                               max_new_tokens=3))
        s = eng.run()
        # fake clock: every timed region is exactly 1s — decode wall
        # is the tick count, strictly less than the run() wall that
        # also covers the two prefills
        assert s.decode_wall_s == eng.steps == s.decode_steps
        assert s.decode_wall_s < s.wall_s
        assert s.decode_tokens_per_sec == pytest.approx(
            eng.decode_tokens / s.decode_wall_s, abs=0.01)
        # 2 requests x 3 tokens, one each from prefill
        assert eng.decode_tokens == s.tokens_generated - 2

    def test_summary_survives_draining_done(self):
        # lifetime totals come from counters, not from re-summing
        # ``done`` — a long-running caller may pop finished requests
        # to keep host memory flat without corrupting the summary
        model, params = _tiny_model()
        lad = BucketLadder(batch=(2,), pages=(2,))
        eng = _engine(model, params, ladder=lad)
        for i, p in enumerate([[2, 5], [7, 1, 3]]):
            eng.submit(Request(rid=f"r{i}", prompt=p,
                               max_new_tokens=3))
        first = eng.run()
        eng.done.clear()                      # caller consumed results
        eng.submit(Request(rid="late", prompt=[4, 4],
                           max_new_tokens=3))
        second = eng.run()
        assert second.requests_done == 3
        assert second.tokens_generated == first.tokens_generated + 3

    def test_eviction_frees_blocks_for_queued_requests(self):
        # pool too small for all three at once: the third request can
        # only be admitted after an earlier one finishes and frees its
        # blocks — the continuous part of continuous batching
        model, params = _tiny_model()
        lad = BucketLadder(batch=(2,), pages=(2,))
        cfg_blocks = 5                       # 4 usable = two requests
        eng = _engine(model, params, ladder=lad,
                      num_blocks=cfg_blocks)
        for i in range(3):
            eng.submit(Request(rid=f"r{i}", prompt=[1 + i, 2],
                               max_new_tokens=4))
        admitted_at = {}

        def watch(step):
            for rid in eng.active:
                admitted_at.setdefault(rid, step)

        summary = eng.run(before_tick=watch)
        assert summary.requests_done == 3
        assert admitted_at["r2"] > 0         # waited for an eviction
        assert eng.manager.free_blocks == cfg_blocks - 1
        assert summary.tokens_per_sec > 0
        assert summary.latency_p50_ms is not None
        assert summary.latency_p99_ms >= summary.latency_p50_ms

    def test_reservation_counts_future_growth(self):
        # admission must reserve the whole worst case NET of what the
        # pool already owes active requests: r0 holds one page but may
        # grow to 4; admitting r1 (worst 3 pages) against the 3 blocks
        # literally free would exhaust the pool mid-decode
        model, params = _tiny_model(max_seq=32)
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2,), pages=(4,)),
                      num_blocks=5)           # 4 usable
        eng.submit(Request(rid="r0", prompt=[1],
                           max_new_tokens=15))   # worst 16 = 4 pages
        eng.submit(Request(rid="r1", prompt=[1, 2],
                           max_new_tokens=10))   # worst 12 = 3 pages
        overlap = []

        def watch(step):
            overlap.append(set(eng.active))

        summary = eng.run(before_tick=watch)     # must not raise
        assert summary.requests_done == 2
        assert not any({"r0", "r1"} <= s for s in overlap)
        assert eng.manager.free_blocks == 4

    def test_clean_drain_on_termination(self):
        class FakeResume:
            source = "sigterm"

            def __init__(self):
                self.calls = 0

            def termination_requested(self):
                self.calls += 1
                return self.calls > 2

        model, params = _tiny_model()
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2,), pages=(2,)),
                      autoresume=FakeResume())
        for i in range(2):
            eng.submit(Request(rid=f"r{i}", prompt=[1, 2 + i],
                               max_new_tokens=5))
        summary = eng.run()
        assert summary.drained
        assert summary.requests_preempted == 2
        assert not eng.active and not eng.queue
        # every block returned to the pool — nothing leaks on drain
        assert eng.manager.free_blocks == \
            eng.cache_cfg.usable_blocks

    def test_drain_accounts_for_queued_requests(self):
        # requests accepted but never admitted (batch ladder keeps
        # them queued) must not vanish on SIGTERM: the drain marks
        # them preempted and lands them in done like everything else
        class FakeResume:
            source = "sigterm"

            def __init__(self):
                self.calls = 0

            def termination_requested(self):
                self.calls += 1
                return self.calls > 2

        model, params = _tiny_model()
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2,), pages=(2,)),
                      autoresume=FakeResume())
        for i in range(5):                   # only 2 admit at once
            eng.submit(Request(rid=f"r{i}", prompt=[1, 2 + i],
                               max_new_tokens=6))
        summary = eng.run()
        assert summary.drained
        assert summary.requests_preempted == 5
        assert {q.rid for q in eng.done} == {f"r{i}" for i in range(5)}
        assert not eng.active and not eng.queue
        assert eng.manager.free_blocks == eng.cache_cfg.usable_blocks


# ---------------------------------------------------------------------------
# bucket-ladder compile discipline + the serve smoke
# ---------------------------------------------------------------------------

class TestCompileDiscipline:
    def test_warmup_compiles_exactly_the_ladder(self):
        model, params = _tiny_model()
        lad = BucketLadder(batch=(1, 2), pages=(1, 2))
        eng = _engine(model, params, ladder=lad)
        compiles = eng.warmup()
        # one prefill per page rung + the full decode ladder product
        assert len(compiles) == len(lad.pages) + \
            len(lad.batch) * len(lad.pages)
        assert all(v == 1 for v in compiles.values())
        before = dict(eng._compiles)
        eng.warmup()                         # idempotent
        assert eng._compiles == before

    def test_serve_smoke_sanitized_one_compile_per_bucket(self):
        # the acceptance criterion: steady-state serving under
        # sanitize() compiles exactly once per bucket (the smoke holds
        # a post-warmup recompile budget of ZERO; a shape leaking past
        # the ladder would raise RecompileBudgetExceeded here)
        lad = BucketLadder(batch=(2, 4), pages=(2,))
        summary, eng = serve_smoke(
            4, max_new_tokens=3, ladder=lad, num_blocks=24,
            block_size=4, sanitize=True, autoresume=None,
            return_engine=True)
        assert summary.requests_done == 4
        assert summary.tokens_per_sec > 0
        assert len(summary.compiles) == \
            len(lad.pages) + len(lad.batch) * len(lad.pages)
        assert all(v == 1 for v in summary.compiles.values())

    def test_serve_smoke_sigterm_clean_drain(self, tmp_path):
        # the real-signal leg: a SIGTERM mid-serve (flag-only handler)
        # stops admissions, frees the pool, marks in-flight requests
        # preempted, and still lands a full summary + JSONL record
        jsonl = tmp_path / "serve.jsonl"
        summary, eng = serve_smoke(
            4, max_new_tokens=6, jsonl=str(jsonl),
            ladder=BucketLadder(batch=(2, 4), pages=(2,)),
            num_blocks=24, block_size=4, fault="sigterm@2",
            return_engine=True)
        assert summary.drained
        assert summary.requests_preempted > 0
        assert eng.manager.free_blocks == eng.cache_cfg.usable_blocks
        text = jsonl.read_text()
        assert "serve_preempt" in text and "serve_done" in text

    def test_serve_smoke_int8_kv(self):
        summary = serve_smoke(2, max_new_tokens=3, kv_dtype="int8",
                              ladder=BucketLadder(batch=(2,),
                                                  pages=(2,)),
                              num_blocks=16, block_size=4,
                              autoresume=None)
        assert summary.requests_done == 2
        assert summary.tokens_generated == 2 * 3
