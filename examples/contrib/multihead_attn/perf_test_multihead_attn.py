"""Self-timed multihead-attention perf harness — the TPU equivalent of
the reference's contrib demo
(ref: apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py).

Sweeps batch (number of sequences) for a stack of attention layers and
prints per-config time + attention TFLOP/s, comparing:

  (default)    impl='fast'  — flash E-layout kernels, in-kernel dropout
  --ref        impl='default' — unfused einsum/softmax reference path
  --encdec-attn  encoder-decoder attention instead of self attention
  --norm-add   include the fused layernorm + residual-add block
  --fwd        forward only (skip the backward)

Timing: K trials inside one jitted lax.scan with a two-K wall-clock
slope (one dispatch per measurement — at ms-scale steps a Python step
loop measures dispatch latency, not the kernels).

Run on the TPU:
  PYTHONPATH=/root/repo python examples/contrib/multihead_attn/perf_test_multihead_attn.py
"""
import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", "..", ".."))

import jax
import jax.numpy as jnp

from apex_tpu.contrib.multihead_attn import (EncdecMultiheadAttn,
                                             SelfMultiheadAttn)

p = argparse.ArgumentParser(description="Multihead Attention perf test")
p.add_argument("--seq-length", default=64, type=int)
p.add_argument("--num-seqs-start", default=10, type=int)
p.add_argument("--num-seqs-stop", default=120, type=int)
p.add_argument("--num-seqs-inc", default=25, type=int)
p.add_argument("--trials", default=8, type=int)
p.add_argument("--layers", default=18, type=int)
p.add_argument("--hidden-dim", default=1024, type=int)
p.add_argument("--heads", default=16, type=int)
p.add_argument("--encdec-attn", action="store_true")
p.add_argument("--norm-add", action="store_true")
p.add_argument("--ref", action="store_true",
               help="unfused reference path (impl='default')")
p.add_argument("--fwd", action="store_true", help="forward only")
p.add_argument("--biases", action="store_true")
p.add_argument("--dropout", default=0.1, type=float)
args = p.parse_args()

impl = "default" if args.ref else "fast"
cls = EncdecMultiheadAttn if args.encdec_attn else SelfMultiheadAttn
layer = cls(embed_dim=args.hidden_dim, num_heads=args.heads,
            dropout=args.dropout, bias=args.biases,
            include_norm_add=args.norm_add, impl=impl)

key = jax.random.PRNGKey(111)


def stack_apply(variables, x, rng):
    """args.layers sequential attention blocks (the reference stacks
    layers to amortize launch overhead; here it also matches real
    encoder depth)."""
    def body(carry, i):
        x, rng = carry
        rng, sub = jax.random.split(rng)
        out = layer.apply(variables, x, x, x, is_training=True,
                          rngs={"dropout": sub})
        y = out[0] if isinstance(out, tuple) else out
        if args.norm_add:
            y = y[0] if isinstance(y, tuple) else y
        return (y.astype(x.dtype), rng), ()
    (x, _), _ = jax.lax.scan(body, (x, rng), jnp.arange(args.layers))
    return x


for seqs in range(args.num_seqs_start, args.num_seqs_stop + 1,
                  args.num_seqs_inc):
    x = jax.random.normal(jax.random.fold_in(key, seqs),
                          (args.seq_length, seqs, args.hidden_dim),
                          jnp.bfloat16) * 0.5
    variables = layer.init({"params": key, "dropout": key}, x, x, x,
                           is_training=True)

    if args.fwd:
        def run_once(x, rng):
            return stack_apply(variables, x, rng)
    else:
        def run_once(x, rng):
            def loss(x):
                return jnp.sum(stack_apply(variables, x, rng)
                               .astype(jnp.float32) ** 2)
            return jax.grad(loss)(x)

    def make_steps(n):
        @jax.jit
        def steps(x):
            def body(carry, i):
                y = run_once(carry, jax.random.fold_in(key, i))
                return (carry + 1e-6 * y.astype(carry.dtype)), ()
            return jax.lax.scan(body, x, jnp.arange(n))[0]
        return steps

    k1, k2 = 2, max(4, args.trials)
    run1, run2 = make_steps(k1), make_steps(k2)
    float(jnp.sum(jnp.ravel(run1(x))[:1]))
    float(jnp.sum(jnp.ravel(run2(x))[:1]))
    best1 = best2 = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        float(jnp.sum(jnp.ravel(run1(x))[:1]))
        best1 = min(best1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(jnp.sum(jnp.ravel(run2(x))[:1]))
        best2 = min(best2, time.perf_counter() - t0)
    sec = (best2 - best1) / (k2 - k1) if best2 > best1 else best2 / k2
    s, b, h, d = (args.seq_length, seqs, args.heads,
                  args.hidden_dim // args.heads)
    # attention-core matmul flops per layer (fwd 2 + bwd 5 matmuls)
    per_layer = (2 if args.fwd else 7) * 2.0 * b * h * s * s * d / 2
    flops = per_layer * args.layers
    print(f"[{impl}{'/encdec' if args.encdec_attn else ''}"
          f"{'/norm_add' if args.norm_add else ''}"
          f"{'/fwd' if args.fwd else ''}] "
          f"seqs={seqs:4d} seq={s} hid={args.hidden_dim}: "
          f"{sec*1e3:8.2f} ms/iter "
          f"({flops/sec/1e12:6.2f} attention TF/s)", flush=True)
