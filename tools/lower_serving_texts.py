"""Write the lowered text of the serving families' prefill, decode and
extend programs, at tiny widths on the CPU, to a directory: run it in two
checkouts and ``diff -r`` the directories to see whether a change to
``apex_tpu/serving`` or ``apex_tpu/ops`` moved a program it was not meant
to (what PR 34 checked by hand and PR 36 with this file; ``PERF.md``
section 6).

    JAX_PLATFORMS=cpu python tools/lower_serving_texts.py /tmp/texts_a
    (cd ../parent && JAX_PLATFORMS=cpu python \
        ../repo/tools/lower_serving_texts.py /tmp/texts_b)
    diff -r /tmp/texts_a /tmp/texts_b && echo the same programs

The models are the test files' own tiny ones (GPT-2 from shapes alone,
``rope_moe`` as ``tests/test_serving_rope_moe.py`` builds it, ``mla_moe``
as ``tests/test_serving_mla_moe.py``), bf16, a cache of 33 blocks of 4;
each step function directly, and each family's decode and prefill again
through ``ServingEngine``'s own jit builders.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "tests")]

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402

from apex_tpu import serving                 # noqa: E402
from apex_tpu.serving import model as sm     # noqa: E402

BF16, F32 = jnp.bfloat16, jnp.float32


def gpt2():
    hid = 128
    cfg = serving.ServingModelConfig(vocab_size=96, hidden_size=hid,
                                     num_heads=2, num_layers=2, max_seq=64,
                                     dtype=BF16)

    def w(*s):
        return jax.ShapeDtypeStruct(s, BF16)

    def f(*s):
        return jax.ShapeDtypeStruct(s, F32)

    layer = sm.LayerWeights(
        ln1_w=f(hid), ln1_b=f(hid), qkv_k=w(hid, 3 * hid), qkv_b=w(3 * hid),
        dense_k=w(hid, hid), dense_b=w(hid), ln2_w=f(hid), ln2_b=f(hid),
        fc1_k=w(hid, 4 * hid), fc1_b=w(4 * hid), fc2_k=w(4 * hid, hid),
        fc2_b=w(hid))
    return cfg, serving.GPTServingWeights(
        wte=w(96, hid), wpe=w(64, hid), layers=(layer,) * 2, lnf_w=f(hid),
        lnf_b=f(hid)), None


def laguna():
    import test_serving_rope_moe as t
    from benchmarks import builders_laguna as b

    cfg = b.serving_config(t.TINY, max_seq=64, dtype=BF16)
    return cfg, jax.eval_shape(lambda: b.make_weights(t.TINY, cfg, 1)), \
        lambda: b.make_weights(t.TINY, cfg, 1)


def openpangu():
    import test_serving_mla_moe as t
    from benchmarks import builders_openpangu as b

    cfg = b.serving_config(t.CONFIG, max_seq=64, dtype=BF16)
    return cfg, jax.eval_shape(lambda: b.make_weights(t.CONFIG, cfg, 1)), \
        lambda: b.make_weights(t.CONFIG, cfg, 1)


def ints(*s):
    return jax.ShapeDtypeStruct(s, jnp.int32)


def main(out: str) -> None:
    os.makedirs(out, exist_ok=True)

    def put(name, text):
        with open(os.path.join(out, name + ".txt"), "w") as f:
            f.write(text)
        print(name, len(text))

    bb, pb, t = 4, 16, 8
    for name, make in (("gpt2", gpt2), ("laguna", laguna),
                       ("openpangu", openpangu)):
        cfg, shapes, real = make()
        ccfg = serving.default_cache_config(cfg, num_blocks=33,
                                            block_size=4, kv_dtype="bf16")
        cache = jax.eval_shape(lambda: serving.init_cache(ccfg))
        steps = dict(
            decode=(sm.gpt_decode_step, (ints(bb), ints(bb), ints(bb, pb),
                                         ints(bb), ints(bb), ints(bb))),
            prefill=(sm.gpt_prefill_step, (ints(32), ints(), ints(8))),
            extend=(sm.gpt_extend_step, (ints(bb, t), ints(bb, pb), ints(bb),
                                         ints(bb, t), ints(bb, t))))
        for step, (fn, data) in steps.items():
            jitted = jax.jit(lambda w, c, *a, fn=fn: fn(w, cfg, ccfg, c, *a),
                             donate_argnums=(1,))
            put(f"{name}_{step}", jitted.lower(shapes, cache, *data)
                .as_text())
        if real is None:
            continue
        eng = serving.ServingEngine(
            real(), cfg, ccfg, ladder=serving.BucketLadder(
                batch=(bb,), pages=(pb,)), speculate_k=0, prefill_chunk=0,
            prefix_share=False, slo=None)
        put(f"{name}_engine_decode", eng._jit_decode().lower(
            *eng._decode_args(bb, pb)).as_text())
        put(f"{name}_engine_prefill", eng._jit_prefill().lower(
            *eng._prefill_args(32)).as_text())
        put(f"{name}_engine_extend", eng._jit_extend().lower(
            *eng._extend_args(1, t, pb)).as_text())


if __name__ == "__main__":
    main(sys.argv[1])
