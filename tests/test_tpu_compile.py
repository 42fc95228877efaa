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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu import serving
from apex_tpu.ops import (flash_attention as fa, flash_decode as fd,
                          fused_pipeline, latent_decode as ld,
                          layer_norm as ln, moe_routing,
                          quant_matmul as qm, scaled_softmax)
from apex_tpu.serving import model as serving_model

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
    for mod in (fa, fd, ld, ln, scaled_softmax, qm, moe_routing,
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


def _flash_e_bert(qkv, kv_mask):
    return fa.flash_attention_e(qkv, kv_mask=kv_mask)


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


# the mla_moe cell (openpangu-ultra-moe): a latent cache of 576-wide rows
# (stored at 640, whole lane tiles) in a pool of 24,577 blocks of 16, read by 128 absorbed query heads;
# batch rung 64 on the 288-page rung, the 2-token verify step, and the
# expanded prefill at QK width 192, V width 128, rungs to 4,096
LAT_BLOCKS, LAT_H, LAT_D, LAT_V, LAT_B = 24577, 128, 640, 512, 64
_LAT_CACHE = ((LAT_BLOCKS, 1, KV_BLOCK, LAT_D), BF16)


def _latent_decode(q, cache, bt, sl):
    attn = ld.latent_decode if q.ndim == 3 else ld.latent_decode_multi
    return attn(q, cache, bt, sl, value_dim=LAT_V, scale=192 ** -0.5)


def _latent_args(pages, t=None):
    q = (LAT_B, LAT_H, LAT_D) if t is None else (LAT_B, t, LAT_H, LAT_D)
    return ((q, BF16), _LAT_CACHE, ((LAT_B, pages), I32), ((LAT_B,), I32))


def _mla_prefill_args(s):
    return (((1, LAT_H, s, 192), BF16), ((1, LAT_H, s, 192), BF16),
            ((1, LAT_H, s, 128), BF16))


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
    # the causal row chunks on a padded sequence, and BERT's route
    # (non-causal through the kv_mask lane, batch 16 x 512)
    "flash_e_fwd_bwd_s1000": (_grad_sum(_flash_e, 1),
                              (((B, 1000, H, 3 * D), BF16),)),
    "flash_e_fwd_bwd_s512_noncausal": (
        _grad_sum(_flash_e_bert, 1),
        (((16, 512, H, 3 * D), BF16), ((16, 512), jnp.bool_))),
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
    # latent attention (serving's third family): the paged latent
    # kernel, eight pages a step, and the flash forward with a value
    # width of its own
    "latent_decode_b64_p288": (_latent_decode, _latent_args(288)),
    "latent_decode_b64_p64": (_latent_decode, _latent_args(64)),
    "latent_decode_multi_t2_b64_p288": (_latent_decode,
                                        _latent_args(288, t=2)),
    "flash_fwd_qk192_v128_s4096": (_flash, _mla_prefill_args(4096)),
    "flash_fwd_qk192_v128_s1024": (_flash, _mla_prefill_args(1024)),
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


def test_e_drivers_lower_one_body_a_kernel(one_chip, mosaic):
    """The E drivers are jitted, so a program that calls
    ``flash_attention_e`` once a layer lowers each distinct kernel once
    (a train step of 24 layers: 2 bodies, not 48).  Lowered for the
    described chip, never compiled."""
    def four_layers(qkv):
        return sum(_flash_e(qkv * k).astype(F32).sum()
                   for k in (1.0, 2.0, 3.0, 4.0))

    qkv = jax.ShapeDtypeStruct((2, S, H, 3 * D), BF16, sharding=one_chip)
    text = jax.jit(jax.grad(four_layers)).lower(qkv).as_text()
    bodies = re.findall(r'kernel_name = "(\w+)"', text)
    assert sorted(bodies) == ["flash_attention_bwd", "flash_attention_fwd"]
    assert text.count("call @_fwd_e_driver") == 4
    assert text.count("call @_bwd_e_driver") == 4


# --- whole serving programs: the paged cache keeps the kernel's layout ----
#
# A decode tick once spent four fifths of its time copying the KV cache
# from the layout the Pallas kernel reads to the one XLA's row scatter
# wanted, and back (PERF.md, PR 30).  The compiled program shows such a
# copy without a chip, so here the decode and the extend step are
# compiled whole at the benchmark cells' sizes, cache donated, and their
# optimized HLO is held to: nothing the size of a layer's cache array
# but the in-place page writes, every cache leaf aliased to its output,
# and temporaries far under one leaf.

TEMP_LIMIT = 64 << 20
# what may produce something cache-sized: a parameter, a view of one,
# and the kernel-free bookkeeping of a tuple
_FREE_OPS = {"parameter", "bitcast", "get-tuple-element", "tuple"}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (?P<type>\(?\w+\[[\d,]*\]\S*) (?P<op>[\w-]+)\(")
# a fusion's backend config where the compiler writes the result over
# operand 0
_OVER_OPERAND_0 = '"aliasing_operands":{"lists":[{"indices":["0",'


def _holds_leaf(result_type: str, leaf_shape) -> bool:
    """Whether an HLO result type has an array of a cache leaf's shape,
    alone or stacked (``[nb,hk,bs,dk]``, ``[1,nb,hk,bs,dk]``, ...; a
    latent leaf ``[nb,1,bs,d]`` also as ``[nb,bs,d]``)."""
    def solid(dims):      # the compiler drops and adds axes of 1 freely
        return [d for d in map(int, filter(None, dims)) if d != 1]

    tail = solid(map(str, leaf_shape))
    return any(solid(dims.split(","))[-len(tail):] == tail
               for dims in re.findall(r"\w+\[([\d,]*)\]", result_type))


def cache_sized_ops(hlo_text: str, leaf_shape):
    """Every instruction of the entry computation whose result is a
    whole cache leaf ``leaf_shape`` = (nb, hk, bs, dk) or a stack of
    them, as ``(opcode, result type, in place)``: ``in place`` where the
    compiler says the result aliases operand 0 (``aliasing_operands``
    of a fusion's backend config), as a page scatter into a donated
    cache array does.  What a reader wants of a serving program's text:
    a ``copy``, ``slice`` or ``fusion`` here that is not in place moves
    a whole layer's cache."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    found = []
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if m and m["op"] not in _FREE_OPS \
                and _holds_leaf(m["type"], leaf_shape):
            found.append((m["op"], m["type"].split("{")[0],
                          _OVER_OPERAND_0 in line))
    return found


def _on(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


GPT2_CFG = serving.ServingModelConfig(
    vocab_size=VOCAB, hidden_size=HID, num_heads=H, num_layers=24,
    max_seq=S, dtype=BF16)


def _gpt2_345m_float32():
    """GPT-2 345M's serving weights as ``extract_serving_weights`` hands
    them over: the trained tree's float32 arrays."""
    def w(*shape):
        return jax.ShapeDtypeStruct(shape, F32)

    layer = serving_model.LayerWeights(
        ln1_w=w(HID), ln1_b=w(HID), qkv_k=w(HID, 3 * HID),
        qkv_b=w(3 * HID), dense_k=w(HID, HID), dense_b=w(HID),
        ln2_w=w(HID), ln2_b=w(HID), fc1_k=w(HID, 4 * HID),
        fc1_b=w(4 * HID), fc2_k=w(4 * HID, HID), fc2_b=w(HID))
    return GPT2_CFG, serving.GPTServingWeights(
        wte=w(VOCAB, HID), wpe=w(S, HID), layers=(layer,) * 24,
        lnf_w=w(HID), lnf_b=w(HID))


def _gpt2_345m():
    """GPT-2 345M as the serving cells run it: the float32 tree as the
    engine holds it (``weights_in_compute_dtype``, what ``ServingEngine``
    makes of the tree it is given): matrices, biases and the two tables
    in bf16, LayerNorm's vectors in float32."""
    cfg, given = _gpt2_345m_float32()
    return cfg, jax.eval_shape(
        lambda w: serving_model.weights_in_compute_dtype(w, cfg), given)


def _laguna_two_layers():
    """The ``rope_moe`` family at Laguna XS.2's widths, a full dense
    layer and a windowed layer of 256 experts."""
    layers = (
        serving.LayerSpec(num_heads=48, window=None, moe=False,
                          rope=serving.RopeSpec(theta=5e5, rotary_dim=64)),
        serving.LayerSpec(num_heads=64, window=GQ_WINDOW, moe=True,
                          rope=serving.RopeSpec(theta=1e4,
                                                rotary_dim=GQ_D)))
    cfg = serving.ServingModelConfig(
        vocab_size=100352, hidden_size=2048, num_heads=48, num_layers=2,
        max_seq=8704, dtype=BF16, layernorm_eps=1e-6, num_experts=256,
        head_dim=GQ_D, num_kv_heads=GQ_KV, family="rope_moe",
        layers=layers, experts_per_token=8, routed_scaling=2.5)
    weights = jax.eval_shape(lambda: serving.init_rope_moe_weights(
        jax.random.PRNGKey(0), cfg, dense_ffn=8192, expert_ffn=512,
        shared_ffn=512))
    return cfg, weights


def _openpangu_two_layers():
    """The ``mla_moe`` family at openPangu-Ultra-MoE's widths, the dense
    layer and a layer that holds 16 of 256 experts, an eighth of the
    vocabulary."""
    rope = serving.RopeSpec(theta=25.6e6, rotary_dim=64)
    cfg = serving.ServingModelConfig(
        vocab_size=19200, hidden_size=7680, num_heads=LAT_H, num_layers=2,
        max_seq=4608, dtype=BF16, num_experts=256, family="mla_moe",
        layers=tuple(serving.LayerSpec(num_heads=LAT_H, window=None,
                                       rope=rope, moe=moe)
                     for moe in (False, True)),
        experts_per_token=8, routed_scaling=2.5,
        mla=serving.MlaSpec(q_rank=1536, kv_rank=LAT_V, nope_dim=128,
                            rope_dim=64, v_dim=128))
    weights = jax.eval_shape(lambda: serving.init_mla_moe_weights(
        jax.random.PRNGKey(0), cfg, dense_ffn=18432, expert_ffn=2048,
        shared_ffn=2048, experts_held=16))
    return cfg, weights


EVA_BLOCKS, EVA_WINDOW, EVA_SEQ = 4097, 2048, 32768


def _evabyte_two_layers():
    """The ``rope_moe`` family's EVA layer at EvaByte's widths: 32
    heads of 128, SwiGLU of 11,008, window 2,048 beside a pooled row a
    16-byte chunk, 320 bytes, eight prediction heads."""
    spec = serving.LayerSpec(
        num_heads=32, window=EVA_WINDOW, moe=False, chunk=KV_BLOCK,
        rope=serving.RopeSpec(theta=1e5, rotary_dim=128))
    cfg = serving.ServingModelConfig(
        vocab_size=320, hidden_size=4096, num_heads=32, num_layers=2,
        max_seq=EVA_SEQ, dtype=BF16, layernorm_eps=1e-5, head_dim=128,
        num_kv_heads=32, family="rope_moe", layers=(spec, spec),
        norm_unit_offset=True, pred_heads=8)
    weights = jax.eval_shape(lambda: serving.init_rope_moe_weights(
        jax.random.PRNGKey(0), cfg, dense_ffn=11008, std=0.01275))
    return cfg, weights


# name -> (model, pool blocks, step, batch rung, page rung, chunk; a
# prefill's chunk is its prompt rung)
PROGRAMS = {
    "gpt2_decode_b4_p64": (_gpt2_345m, CELL_BLOCKS, "decode", 4, 64, 0),
    "gpt2_prefill_256": (_gpt2_345m, CELL_BLOCKS, "prefill", 1, 16, 256),
    "gpt2_decode_b16_p64": (_gpt2_345m, CELL_BLOCKS, "decode", 16, 64, 0),
    "gpt2_decode_b32_p64": (_gpt2_345m, CELL_BLOCKS, "decode", 32, 64, 0),
    "gpt2_extend_b8_t8_p64": (_gpt2_345m, CELL_BLOCKS, "extend", 8, 64, 8),
    "gpt2_extend_b1_t256_p64": (_gpt2_345m, CELL_BLOCKS, "extend",
                                1, 64, 256),
    "laguna_decode_b32_p544": (_laguna_two_layers, GQ_BLOCKS, "decode",
                               32, 544, 0),
    "laguna_extend_b8_t8_p544": (_laguna_two_layers, GQ_BLOCKS, "extend",
                                 8, 544, 8),
    "openpangu_decode_b64_p288": (_openpangu_two_layers, LAT_BLOCKS,
                                  "decode", LAT_B, 288, 0),
    "openpangu_extend_b64_t2_p288": (_openpangu_two_layers, LAT_BLOCKS,
                                     "extend", LAT_B, 288, 2),
    "evabyte_decode_b16_p256": (_evabyte_two_layers, EVA_BLOCKS, "decode",
                                16, 256, 0),
    "evabyte_chunk_2048": (_evabyte_two_layers, EVA_BLOCKS, "prefill",
                           1, 128, EVA_WINDOW),
    "evabyte_chunk_512": (_evabyte_two_layers, EVA_BLOCKS, "prefill",
                          1, 32, 512),
}


def compile_serving_step(name, sharding, make=None):
    """``PROGRAMS[name]`` compiled for the chip ``sharding`` describes,
    the cache donated: ``(compiled, cache config, number of weight
    leaves)``; the cache's leaves are the parameters after those.
    ``make`` stands another model in for the program's own."""
    own, blocks, step, bb, pb, t = PROGRAMS[name]
    cfg, weights = (make or own)()
    ccfg = serving.default_cache_config(cfg, num_blocks=blocks,
                                        block_size=KV_BLOCK,
                                        kv_dtype="bf16")
    cache = jax.eval_shape(lambda: serving.init_cache(ccfg))

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=sharding)

    # the pooled cache: a decode step is told where a completed page
    # pools to, a prefill is one window-aligned chunk (its start, the
    # summary pages before it, those its own pages pool to)
    if step == "decode":
        fn, data = serving_model.gpt_decode_step, (
            ints(bb), ints(bb), ints(bb, pb), ints(bb), ints(bb), ints(bb))
        if ccfg.pooled:
            data += (ints(bb), ints(bb))
    elif step == "prefill":
        fn, data = serving_model.gpt_prefill_step, (
            ints(t), ints(), ints(t // KV_BLOCK))
        if ccfg.pooled:
            data += (ints(), ints((cfg.max_seq - 1) // ccfg.window
                                  * ccfg.window_summary_pages),
                     ints(t // KV_BLOCK ** 2))
    else:
        fn, data = serving_model.gpt_extend_step, (
            ints(bb, t), ints(bb, pb), ints(bb), ints(bb, t), ints(bb, t))
    jitted = jax.jit(lambda w, c, *a: fn(w, cfg, ccfg, c, *a),
                     donate_argnums=(1,))
    compiled = jitted.lower(_on(weights, sharding), _on(cache, sharding),
                            *data).compile()
    return compiled, ccfg, len(jax.tree.leaves(weights))


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_serving_step_keeps_cache_layout(name, one_chip, mosaic,
                                         no_persistent_cache):
    compiled, ccfg, n_weights = compile_serving_step(name, one_chip)
    text = compiled.as_text()
    leaves = (1 if ccfg.latent else 2) * ccfg.num_layers
    ops = cache_sized_ops(text, ccfg.kv_shape)
    moved = [op for op in ops if not op[2]]
    assert not moved, f"cache-sized ops that are not in place: {moved}"
    # a layer's k and v (or its latents) are each written once, where
    # they lie; the pooled cache twice, the new rows to the window's
    # pages and the pooled rows to the summary pages
    assert len(ops) == leaves * (2 if ccfg.pooled else 1), ops
    header = text[:text.index("\n")]
    aliased = {int(p) for p in re.findall(
        r"\{\d+\}: \((\d+), \{\}, may-alias\)", header)}
    assert aliased == set(range(n_weights, n_weights + leaves)), header
    temp = compiled.memory_analysis().temp_size_in_bytes
    # a 2,048-byte chunk's float32 activations at these widths are
    # 32-86 MiB each (277 MiB of temporaries in all): whole activations,
    # far from a cache leaf's 2 GiB
    limit = 5 * TEMP_LIMIT if name == "evabyte_chunk_2048" else TEMP_LIMIT
    assert temp < limit, f"{temp / 2**20:.0f} MiB of temporaries"


_DEFINITION = re.compile(r"^\s*(?:ROOT )?(%[\w.-]+) = (\w+)\[([\d,]*)\]")
_CONVERT = re.compile(r" convert\((%[\w.-]+)\)")


def _dims(text: str):
    return tuple(int(d) for d in text.split(",") if d)


def float32_weight_reads(hlo_text: str, weights):
    """What a program that is handed float32 matrices shows: ``(the
    float32 parameters of its entry, the ``convert`` ops whose operand
    is float32 of a weight matrix's shape)``, each as a list of shapes.
    ``weights`` gives the matrices' shapes; an activation has a batch or
    prompt rung in front and none of them."""
    matrices = {tuple(leaf.shape) for leaf in jax.tree.leaves(weights)
                if len(leaf.shape) > 1}
    lines = hlo_text.splitlines()
    header = next(line for line in lines if line.startswith("ENTRY "))
    params = [_dims(d) for d in re.findall(r": f32\[([\d,]*)\]",
                                           header.split(") -> ")[0])]
    types = {m[1]: (m[2], _dims(m[3]))
             for m in map(_DEFINITION.match, lines) if m}
    converts = []
    for m in filter(None, map(_CONVERT.search, lines)):
        dtype, dims = types.get(m[1], (None, ()))
        if dtype == "f32" and (dims in matrices or dims[::-1] in matrices):
            converts.append(dims)
    return params, converts


GPT2_CELL_PROGRAMS = ["gpt2_decode_b4_p64", "gpt2_prefill_256"]


@pytest.mark.parametrize("name", GPT2_CELL_PROGRAMS)
def test_held_gpt2_weights_are_read_as_they_lie(name, one_chip, mosaic,
                                                no_persistent_cache):
    """The tick and the 256-token prefill of `gpt2-345m.serve-chat`, on
    what the engine makes of a float32 tree: no matrix comes in as
    float32, none is cast in the program."""
    compiled, _, n_weights = compile_serving_step(name, one_chip)
    assert n_weights == 292
    params, converts = float32_weight_reads(compiled.as_text(),
                                            _gpt2_345m()[1])
    # LayerNorm's scale and shift, twice a layer and once at the end
    assert params == [(HID,)] * (2 + 24 * 4), params
    assert not converts, f"float32 matrices cast in the step: {converts}"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_LIMIT, f"{temp / 2**20:.0f} MiB of temporaries"


@pytest.mark.parametrize("name", GPT2_CELL_PROGRAMS)
def test_float32_gpt2_weights_given_to_the_step_show(name, one_chip, mosaic,
                                                     no_persistent_cache):
    """The control of the test above, and what every tick did before the
    engine held its weights: the float32 tree as an argument of the step
    is read whole (292 float32 parameters, 1.42 GB) and every matrix
    cast, the embedding table's 103 MB among the temporaries."""
    compiled, _, _ = compile_serving_step(name, one_chip,
                                          make=_gpt2_345m_float32)
    params, converts = float32_weight_reads(compiled.as_text(),
                                            _gpt2_345m()[1])
    assert len(params) == 292, params
    assert len(converts) == 2 + 24 * 4, converts
    assert compiled.memory_analysis().temp_size_in_bytes > TEMP_LIMIT
