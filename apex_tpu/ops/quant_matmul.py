"""Pallas int8 weight-only matmul: the Q8 serving compute tier.

The serving decode step is a bandwidth problem: every generated token
re-reads every weight matrix once, so at batch 1..8 the GEMV's cost is
the bytes of the kernel operand, not the FLOPs.  Storing weights as
**per-output-channel symmetric int8** (one fp32 scale per output
column) halves-to-quarters that traffic versus bf16/fp32 and follows
the weight-only-quantization serving playbook (LLM.int8()/AWQ-style
inference): activations stay high precision, weights dequantize
tile-by-tile in VMEM inside the kernel, accumulation is fp32, and the
per-channel scale is applied ONCE to the accumulated tile — which is
mathematically identical to dequantize-then-matmul (the scale
distributes over the contraction) but never materializes an fp32
weight tensor in HBM.  That residency guarantee is what the APX606
compiled-graph rule enforces for Q8 entry points; this module is the
one sanctioned dequant site.

Two kernel shapes, one contract:

* :func:`_quant_gemv` — the decode fast path (M <= 8 rows): the whole
  activation block stays resident, grid (N tiles, K tiles), fp32
  scratch accumulator carried over the K dimension.
* :func:`_quant_tiled` — the prefill path: grid (M tiles, N tiles,
  K tiles) for activation matrices that do not fit a single block row.

Quantization (:func:`quantize_weight`) mirrors the serving KV cache's
row discipline (:func:`~apex_tpu.serving.kv_cache.quantize_kv_rows`):
``scale = max(amax, 1e-8) / 127`` — the floor makes an all-zero output
channel round-trip exactly (0 / scale = 0, 0 * scale = 0, never NaN).

The jnp twin is :func:`quant_matmul_reference` — scale-after-matmul in
fp32, the CPU/interpret oracle the parity audit (APX401/402) pins the
kernels to and the XLA fallback :func:`quant_matmul` dispatches to off
TPU (the twin-as-fallback discipline of :mod:`.flash_decode`).

Inference-only: no VJP (quantized weights are a deployment artifact,
never differentiated through).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["quant_matmul", "quant_matmul_reference", "quantize_weight",
           "dequantize_weight", "quantize_weights",
           "is_quantized_weights", "QuantLayerWeights",
           "QuantGPTServingWeights", "SCALE_FLOOR", "self_check"]

# Degenerate-channel floor, shared discipline with the KV cache's
# per-row quantizer: an all-zero output channel gets scale 1e-8/127,
# quantizes to 0, and dequantizes to exactly 0.0 — no 0/0 NaN.
SCALE_FLOOR = 1e-8

# int8 operand tiles are (32, 128) minimum on TPU; fp32 activations
# (8, 128).  The GEMV path pads M to one fp32 sublane tile.
_BM_GEMV = 8
_BM_TILED = 128
_BK = 128
_BN = 128


# ---------------------------------------------------------------------------
# quantize / dequantize
# ---------------------------------------------------------------------------

def quantize_weight(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(K, N) float weight -> ``(wq int8 (K, N), scale f32 (N,))``,
    symmetric per-output-channel: ``w ~= wq * scale`` columnwise."""
    w = jnp.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"quantize_weight expects (K, N), got {w.shape}")
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.maximum(amax, SCALE_FLOOR) / 127.0
    wq = jnp.clip(jnp.round(wf / scale), -127.0, 127.0).astype(jnp.int8)
    return wq, scale


def dequantize_weight(wq: jnp.ndarray, scale: jnp.ndarray,
                      dtype: Any = jnp.float32) -> jnp.ndarray:
    """``wq * scale`` back to a dense float weight (test/debug helper —
    production math never materializes this outside a kernel tile)."""
    return (wq.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
            ).astype(dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int,
                k_axis: int):
    """One (.., N tile, K tile) program: int8 tile -> fp32 in VMEM,
    fp32 accumulate over K, per-channel scale applied once at the
    final K step (scale distributes over the contraction)."""
    k = pl.program_id(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)        # the sanctioned dequant
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        o_ref[...] = (acc_ref[...]
                      * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _quant_gemv(x, wq, scale2, out_dtype):
    """Decode fast path: x (M<=8 padded, K), grid (N tiles, K tiles) —
    the whole activation block rides every program."""
    m, kd = x.shape
    _, n = wq.shape
    n_k = kd // _BK

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n // _BN, n_k),
        in_specs=[
            pl.BlockSpec((m, _BK), lambda j, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BK, _BN), lambda j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BN), lambda j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((m, _BN), lambda j, k: (0, j),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((m, _BN), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k, k_axis=1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=_interpret())(x, wq, scale2)


def _quant_tiled(x, wq, scale2, out_dtype):
    """Prefill path: grid (M tiles, N tiles, K tiles)."""
    m, kd = x.shape
    _, n = wq.shape
    n_k = kd // _BK

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(m // _BM_TILED, n // _BN, n_k),
        in_specs=[
            pl.BlockSpec((_BM_TILED, _BK), lambda i, j, k: (i, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_BK, _BN), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _BN), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_BM_TILED, _BN),
                               lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((_BM_TILED, _BN), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k, k_axis=2),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=_interpret())(x, wq, scale2)


def _pad_to(v: int, grain: int) -> int:
    return -(-v // grain) * grain


def quant_matmul(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                 *, out_dtype: Any = None,
                 backend: Optional[str] = None) -> jnp.ndarray:
    """``x @ (wq * scale)`` without ever building ``wq * scale``:
    fp32 accumulation, per-output-channel scale applied to the
    accumulated product.

    ``x`` is (..., K) in any float dtype, ``wq`` (K, N) int8, ``scale``
    (N,) fp32.  ``backend``: ``None`` picks the Pallas kernels on TPU
    and the jnp twin elsewhere (the XLA-fallback discipline the parity
    registry sanctions); ``"pallas"`` / ``"xla"`` force a side for
    parity tests.  Odd K/N are zero-padded to kernel tiles (a zero K
    tail contributes nothing; padded N columns are sliced off)."""
    x = jnp.asarray(x)
    wq = jnp.asarray(wq)
    scale = jnp.asarray(scale)
    if wq.dtype != jnp.int8:
        raise ValueError(f"wq must be int8, got {wq.dtype}")
    if wq.ndim != 2 or scale.ndim != 1 \
            or scale.shape[0] != wq.shape[1]:
        raise ValueError(
            f"wq (K, N) / scale (N,) mismatch: {wq.shape} vs "
            f"{scale.shape}")
    if x.shape[-1] != wq.shape[0]:
        raise ValueError(
            f"contraction mismatch: x {x.shape} vs wq {wq.shape}")
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if backend not in (None, "pallas", "xla"):
        raise ValueError(f"backend {backend!r} not in "
                         f"(None, 'pallas', 'xla')")
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend == "xla":
        return quant_matmul_reference(x, wq, scale, out_dtype=out_dtype)

    lead = x.shape[:-1]
    kd, n = wq.shape
    x2 = x.reshape(-1, kd)
    m = x2.shape[0]
    kp, np_ = _pad_to(kd, _BK), _pad_to(n, _BN)
    mp = _BM_GEMV if m <= _BM_GEMV else _pad_to(m, _BM_TILED)
    if (mp, kp) != (m, kd):
        x2 = jnp.pad(x2, ((0, mp - m), (0, kp - kd)))
    if (kp, np_) != (kd, n):
        wq = jnp.pad(wq, ((0, kp - kd), (0, np_ - n)))
    scale2 = scale.astype(jnp.float32).reshape(1, n)
    if np_ != n:
        scale2 = jnp.pad(scale2, ((0, 0), (0, np_ - n)))
    run = _quant_gemv if mp == _BM_GEMV else _quant_tiled
    out = run(x2, wq, scale2, out_dtype)
    return out[:m, :n].reshape(*lead, n)


def quant_matmul_reference(x: jnp.ndarray, wq: jnp.ndarray,
                           scale: jnp.ndarray, *,
                           out_dtype: Any = None) -> jnp.ndarray:
    """The jnp twin: fp32 matmul against the raw int8 codes with the
    per-channel scale applied AFTER the contraction — bit-for-bit the
    kernel's math (the scale distributes over the sum), and faster
    than dequantize-premultiply on every backend because the (K, N)
    fp32 weight tensor is never built ahead of the gemm."""
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), wq.astype(jnp.float32),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale.astype(jnp.float32)).astype(out_dtype)


# ---------------------------------------------------------------------------
# GPT serving weight pytrees (the offline conversion target)
# ---------------------------------------------------------------------------

class QuantLayerWeights(NamedTuple):
    """One transformer layer with int8 matmul kernels + per-column
    scales.  Field order mirrors :class:`~apex_tpu.serving.model.
    LayerWeights` with a ``*_s`` scale after each quantized kernel —
    the serving step functions dispatch on the presence of these
    fields (``getattr(lw, "qkv_s", None)``), so the same traced code
    serves both pytrees."""

    ln1_w: jnp.ndarray
    ln1_b: jnp.ndarray
    qkv_k: jnp.ndarray        # (H, 3H) int8
    qkv_s: jnp.ndarray        # (3H,) f32
    qkv_b: jnp.ndarray
    dense_k: jnp.ndarray      # (H, H) int8
    dense_s: jnp.ndarray      # (H,) f32
    dense_b: jnp.ndarray
    ln2_w: jnp.ndarray
    ln2_b: jnp.ndarray
    fc1_k: jnp.ndarray        # (H, F) int8
    fc1_s: jnp.ndarray        # (F,) f32
    fc1_b: jnp.ndarray
    fc2_k: jnp.ndarray        # (F, H) int8
    fc2_s: jnp.ndarray        # (H,) f32
    fc2_b: jnp.ndarray


class QuantGPTServingWeights(NamedTuple):
    """Q8 model pytree: layer matmuls int8, embeddings / layer norms /
    biases / LM head untouched (the tied ``wte`` head stays high
    precision — logit argmax is the one consumer where 8-bit error
    flips tokens)."""

    wte: jnp.ndarray
    wpe: jnp.ndarray
    layers: Tuple[QuantLayerWeights, ...]
    lnf_w: jnp.ndarray
    lnf_b: jnp.ndarray


def quantize_weights(weights) -> QuantGPTServingWeights:
    """Offline conversion of a :class:`~apex_tpu.serving.model.
    GPTServingWeights`-shaped pytree (duck-typed — this module sits
    below serving) to the Q8 deployment artifact."""
    if not hasattr(weights, "wpe"):
        raise ValueError(
            f"quantize_weights: {type(weights).__name__} is not the "
            f"'gpt2' family's weights; the 'rope_moe' and 'mla_moe' "
            f"families have no Q8 layout yet (their expert stacks, and "
            f"latent attention's absorbed projections, have no int8 "
            f"kernel)")
    layers = []
    for lw in weights.layers:
        qkv_k, qkv_s = quantize_weight(lw.qkv_k)
        dense_k, dense_s = quantize_weight(lw.dense_k)
        fc1_k, fc1_s = quantize_weight(lw.fc1_k)
        fc2_k, fc2_s = quantize_weight(lw.fc2_k)
        layers.append(QuantLayerWeights(
            ln1_w=lw.ln1_w, ln1_b=lw.ln1_b,
            qkv_k=qkv_k, qkv_s=qkv_s, qkv_b=lw.qkv_b,
            dense_k=dense_k, dense_s=dense_s, dense_b=lw.dense_b,
            ln2_w=lw.ln2_w, ln2_b=lw.ln2_b,
            fc1_k=fc1_k, fc1_s=fc1_s, fc1_b=lw.fc1_b,
            fc2_k=fc2_k, fc2_s=fc2_s, fc2_b=lw.fc2_b))
    return QuantGPTServingWeights(
        wte=weights.wte, wpe=weights.wpe, layers=tuple(layers),
        lnf_w=weights.lnf_w, lnf_b=weights.lnf_b)


def is_quantized_weights(weights) -> bool:
    """True when ``weights`` carries int8 matmul kernels (structural
    check the engine's swap path uses to tell a requantization from a
    same-shape refresh)."""
    layers = getattr(weights, "layers", ())
    return bool(layers) and hasattr(layers[0], "qkv_s")


def self_check() -> None:
    """Interpret-mode kernel-vs-twin parity on CI-sized shapes — the
    tools/ci.sh quant audit step (the :mod:`.fused_pipeline`
    ``self_check`` pattern).  Raises on divergence."""
    import numpy as np

    rng = np.random.default_rng(0)
    for m, kd, n in ((1, 96, 160), (4, 128, 384), (8, 256, 256),
                     (160, 128, 256)):
        w = jnp.asarray(rng.standard_normal((kd, n)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((m, kd)), jnp.float32)
        wq, sc = quantize_weight(w)
        got = quant_matmul(x, wq, sc, backend="pallas")
        want = quant_matmul_reference(x, wq, sc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # degenerate channel: exact zero round-trip, no NaN
    w = jnp.zeros((64, 32), jnp.float32)
    wq, sc = quantize_weight(w)
    out = quant_matmul(jnp.ones((2, 64)), wq, sc, backend="pallas")
    if not bool(jnp.all(out == 0.0)):
        raise AssertionError("all-zero channel did not round-trip to 0")
