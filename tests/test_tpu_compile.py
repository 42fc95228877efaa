"""Ask the TPU's compiler, without a chip, whether it accepts the Pallas
kernels at GPT-2 345M shapes (on-chip-measurement guide, section 2).

Interpret mode — what every other kernel test runs in on the CPU —
checks the math and says nothing about Mosaic: block shapes it refuses,
casts it has no width for.  Here each kernel is lowered for a
*described* v5e (no device attached) and must come out as a
``tpu_custom_call``.  Nothing runs, so nothing is said about results or
times.

The topology is described inside a fixture: only one process may load
libtpu, and xdist workers all import this file.  All compile cases live
in this one file for the same reason.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu.ops import (flash_attention as fa, flash_decode as fd,
                          fused_pipeline, layer_norm as ln, moe_routing,
                          quant_matmul as qm, scaled_softmax)

# GPT-2 345M: 16 heads of 64, hidden 1024, vocab 50304; train batch 8 x
# seq 1024; serving batch 8, KV block 16, 64 pages (= 1024 tokens).
B, S, H, D, HID, VOCAB = 8, 1024, 16, 64, 1024, 50304
KV_BLOCK, PAGES = 16, 64
N_BLOCKS = B * PAGES + 1
BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever the plugin raises: no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip executable is written to the persistent cache
    but cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer every kernel module off interpret mode.  flash_decode,
    quant_matmul and moe_routing import ``_interpret`` by value, so each
    module's own name is patched."""
    for mod in (fa, fd, ln, scaled_softmax, qm, moe_routing,
                fused_pipeline.fused_optim):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _grad_sum(fn, n_args):
    """Forward + backward of ``fn`` w.r.t. its first ``n_args``."""
    return jax.grad(lambda *a: fn(*a).astype(F32).sum(),
                    argnums=tuple(range(n_args)))


# --- the cases: name -> (function, argument shapes) -----------------------

def _flash(q, k, v):
    return fa.flash_attention(q, k, v, causal=True)


def _flash_e(qkv):
    return fa.flash_attention_e(qkv, causal=True)


def _flash_e_drop(qkv, seed):
    return fa.flash_attention_e(qkv, causal=True, dropout_rate=0.1,
                                dropout_seed=seed)


def _layer_norm(x, g, b):
    return ln.layer_norm(x, g, b)


def _causal_softmax(x):
    return scaled_softmax.scaled_upper_triang_masked_softmax(x, 0.125)


def _quant_matmul(x, wq, s):
    return qm.quant_matmul(x, wq, s, backend="pallas")


def _moe_route(x, logits):
    return moe_routing.moe_route_dispatch(
        x, logits, capacity=320, top_k=2, backend="pallas")


def _grad_norm_finite(buf):
    return fused_pipeline.grad_norm_finite(
        [buf], 0.5, use_pallas=True, interpret=False)


def _adam_pipeline(g, p, m, v):
    return fused_pipeline.adam_pipeline(
        g, p, m, v, grad_scale=0.5, lr=1e-4, beta1=0.9, beta2=0.95,
        eps=1e-8, weight_decay=0.01, bias_correction1=0.1,
        bias_correction2=0.05, lowp_dtype=BF16, use_pallas=True,
        interpret=False)


def _decode(q, k, v, bt, sl, *scales):
    ks, vs = scales if scales else (None, None)
    return fd.flash_decode(q, k, v, bt, sl, k_scale=ks, v_scale=vs)


def _decode_multi(q, k, v, bt, sl, *scales):
    ks, vs = scales if scales else (None, None)
    return fd.flash_decode_multi(q, k, v, bt, sl, k_scale=ks, v_scale=vs)


_QKV = ((B, H, S, D), BF16)
_PACKED = ((N_BLOCKS, H // 2, KV_BLOCK, 2 * D), BF16)
_UNPACKED = ((N_BLOCKS, H, KV_BLOCK, D), BF16)
_TABLES = (((B, PAGES), I32), ((B,), I32))
_KV_SCALE = ((N_BLOCKS, H, KV_BLOCK), F32)
_FLAT = ((HID * 4 * HID,), F32)          # one fc1 weight, packed flat
# the benchmark's serving cells: batch rung 32, a pool of 2,049 blocks,
# page rungs 64 and 16
CELL_B, CELL_BLOCKS = 32, 2049
_CELL_CACHE = ((CELL_BLOCKS, H // 2, KV_BLOCK, 2 * D), BF16)


def _cell_decode_args(pages):
    return ((((CELL_B, H, D), BF16), _CELL_CACHE, _CELL_CACHE)
            + (((CELL_B, pages), I32), ((CELL_B,), I32)))


def _cell_extend_args(t):
    """One row's ``t``-token chunk against the cells' cache: what the
    engine's warm-up compiles a chunk rung when ``prefill_chunk`` or
    ``prefix_share`` is on (64 pages = the 1,024-token rung)."""
    return ((((1, t, H, D), BF16), _CELL_CACHE, _CELL_CACHE)
            + (((1, 64), I32), ((1,), I32)))


# 64 heads of 128 in 64-token blocks: a (hk, bs, dk) block is past the
# kernel's VMEM budget, so a step takes a divisor of the heads
_WIDE = ((B * 4 + 1, 64, 64, 128), BF16)


# 24 heads of 128 in 128-token blocks: 12 head groups would fit the
# budget, but a step's queries must be a sublane tile, so it takes 8;
# and 20 heads, which no tile divides: every head, over the budget
_ODD = {hk: ((B * 4 + 1, hk, 128, 128), BF16) for hk in (24, 20)}


def _odd_decode_args(hk):
    return (((B, hk, 128), BF16), _ODD[hk], _ODD[hk],
            ((B, 4), I32), ((B,), I32))


# the rope_moe cell (laguna-xs2): 8 cache heads of 128 in a pool of
# 12,289 blocks of 16, read by 48 (full layers) or 64 (window 512) query
# heads; batch rung 32 on the 544-page rung; prefill rungs to 8,704
GQ_BLOCKS, GQ_KV, GQ_D, GQ_WINDOW = 12289, 8, 128, 512
_GQ_CACHE = ((GQ_BLOCKS, GQ_KV, KV_BLOCK, GQ_D), BF16)


def _grouped_decode(window):
    def fn(q, k, v, bt, sl):
        return fd.flash_decode(q, k, v, bt, sl, window=window)
    return fn


def _grouped_decode_args(heads, pages):
    return (((CELL_B, heads, GQ_D), BF16), _GQ_CACHE, _GQ_CACHE,
            ((CELL_B, pages), I32), ((CELL_B,), I32))


def _grouped_prefill(window):
    def fn(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window)
    return fn


def _grouped_prefill_args(heads, s):
    return (((1, heads, s, GQ_D), BF16), ((1, GQ_KV, s, GQ_D), BF16),
            ((1, GQ_KV, s, GQ_D), BF16))


def _qmm_args(m, n):
    return (((m, HID), BF16), ((HID, n), I8), ((n,), F32))


CASES = {
    # compiled for the described chip before this file existed
    "flash_fwd": (_flash, (_QKV,) * 3),
    "flash_fwd_bwd": (_grad_sum(_flash, 3), (_QKV,) * 3),
    "flash_e_fwd_bwd_s1024": (_grad_sum(_flash_e, 1),
                              (((B, S, H, 3 * D), BF16),)),
    "flash_e_fwd_bwd_s2048": (_grad_sum(_flash_e, 1),
                              (((4, 2048, H, 3 * D), BF16),)),
    "flash_e_dropout_fwd_bwd": (_grad_sum(_flash_e_drop, 1),
                                (((B, S, H, 3 * D), BF16), ((), I32))),
    "layer_norm_fwd_bwd": (_grad_sum(_layer_norm, 3),
                           (((B * S, HID), BF16), ((HID,), F32),
                            ((HID,), F32))),
    "causal_softmax": (_causal_softmax, (((B * H, S, S), BF16),)),
    "quant_matmul_m1_fc1": (_quant_matmul, _qmm_args(1, 4 * HID)),
    "quant_matmul_m8_fc1": (_quant_matmul, _qmm_args(8, 4 * HID)),
    "quant_matmul_m512_fc1": (_quant_matmul, _qmm_args(512, 4 * HID)),
    "quant_matmul_m1_head": (_quant_matmul, _qmm_args(1, VOCAB)),
    "quant_matmul_m8_head": (_quant_matmul, _qmm_args(8, VOCAB)),
    "quant_matmul_m512_head": (_quant_matmul, _qmm_args(512, VOCAB)),
    # the serving decode path: refused by Mosaic before this PR
    "flash_decode_bf16_packed": (
        _decode, (((B, H, D), BF16), _PACKED, _PACKED) + _TABLES),
    "flash_decode_bf16_unpacked": (
        _decode, (((B, H, D), BF16), _UNPACKED, _UNPACKED) + _TABLES),
    "flash_decode_int8_packed": (
        _decode, (((B, H, D), BF16),
                  (_PACKED[0], I8), (_PACKED[0], I8)) + _TABLES
        + (_KV_SCALE, _KV_SCALE)),
    "flash_decode_int8_unpacked": (
        _decode, (((B, H, D), BF16),
                  (_UNPACKED[0], I8), (_UNPACKED[0], I8)) + _TABLES
        + (_KV_SCALE, _KV_SCALE)),
    "flash_decode_cell_b32_p64": (_decode, _cell_decode_args(64)),
    "flash_decode_cell_b32_p16": (_decode, _cell_decode_args(16)),
    "flash_decode_heads_split": (
        _decode, (((B, 64, 128), BF16), _WIDE, _WIDE,
                  ((B, 4), I32), ((B,), I32))),
    "flash_decode_heads_split_24": (_decode, _odd_decode_args(24)),
    "flash_decode_heads_whole_20": (_decode, _odd_decode_args(20)),
    "flash_decode_multi_t4_packed": (
        _decode_multi, (((B, 4, H, D), BF16), _PACKED, _PACKED)
        + _TABLES),
    "flash_decode_multi_t4_int8_packed": (
        _decode_multi, (((B, 4, H, D), BF16),
                        (_PACKED[0], I8), (_PACKED[0], I8)) + _TABLES
        + (_KV_SCALE, _KV_SCALE)),
    "flash_decode_multi_cell_t256": (_decode_multi, _cell_extend_args(256)),
    "flash_decode_multi_cell_t1024": (_decode_multi,
                                      _cell_extend_args(1024)),
    # grouped query heads and a causal window (serving's second family)
    "flash_decode_grouped_full_b32_p544": (
        _grouped_decode(None), _grouped_decode_args(48, 544)),
    "flash_decode_grouped_window_b32_p544": (
        _grouped_decode(GQ_WINDOW), _grouped_decode_args(64, 544)),
    "flash_decode_grouped_window_b32_p64": (
        _grouped_decode(GQ_WINDOW), _grouped_decode_args(64, 64)),
    "flash_fwd_grouped_full_s8704": (
        _grouped_prefill(None), _grouped_prefill_args(48, 8704)),
    "flash_fwd_grouped_window_s8704": (
        _grouped_prefill(GQ_WINDOW), _grouped_prefill_args(64, 8704)),
    "flash_fwd_grouped_window_s1024": (
        _grouped_prefill(GQ_WINDOW), _grouped_prefill_args(64, 1024)),
    # off the 345M path (MoE routing; optimizer sweeps, off by default)
    "moe_route_dispatch": (_moe_route, (((S, HID), BF16),   # one prompt
                                        ((S, 8), F32))),    # 8 experts
    "grad_norm_finite": (_grad_norm_finite, (_FLAT,)),
    "adam_pipeline": (_adam_pipeline, (_FLAT,) * 4),
}

# A kernel off the 345M path that the compiler still refuses after an
# honest try is not hidden: strict xfail with the compiler's message.
XFAIL = {
    # Replacing the cumsum (triangular matmul) only reaches the next
    # refusal — "infer-vector-layout: unsupported shape cast",
    # tpu.reshape (2x128xi32) -> (256x1xi32), the choice-major flatten —
    # and behind that sit per-token scalar reads of VMEM refs in the
    # scatter loop.  The kernel has only ever run interpreted; it needs
    # a Mosaic-shaped rewrite (ROADMAP S7), not a patch.
    "moe_route_dispatch":
        "NotImplementedError: Unimplemented primitive in Pallas TPU "
        "lowering for KernelType.TC: cumsum",
}


def _params():
    for name in CASES:
        marks = ()
        if name in XFAIL:
            marks = (pytest.mark.xfail(strict=True, reason=XFAIL[name]),)
        yield pytest.param(name, marks=marks, id=name)


@pytest.mark.parametrize("name", list(_params()))
def test_kernel_compiles_for_v5e(name, one_chip, mosaic,
                                 no_persistent_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
