"""The builder of the ``evabyte`` configuration: from its published
``config.json`` keys and a ``serve_open_loop`` traffic file to the
program's own serving engine (``apex_tpu.serving``): the ``rope_moe``
family's dense layer as an EVA layer (an aligned window beside pooled
chunks, over the pooled cache), seeded random weights made in bf16 on
the device in one jitted call, and ``reference_evabyte`` as the plain
reference.  The traffic's ``engine.prefill_chunk`` (the window) goes to
the engine: a prompt is prefilled a window a chunk.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from . import reference_evabyte
from .builders import ServeJob, fold_seed


def serving_config(config: dict, *, max_seq: int, dtype,
                   prefill_flash: bool = True,
                   decode_attention: str = "kernel"):
    """The published keys as the program's ``ServingModelConfig``."""
    from apex_tpu.serving import LayerSpec, RopeSpec, ServingModelConfig

    heads = config["num_attention_heads"]
    d = config["hidden_size"] // heads
    n = config["num_hidden_layers"]
    spec = LayerSpec(
        num_heads=heads, window=config["window_size"],
        rope=RopeSpec(theta=float(config["rope_theta"]), rotary_dim=d),
        moe=False, chunk=config["chunk_size"])
    return ServingModelConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"], num_heads=heads, num_layers=n,
        max_seq=max_seq, dtype=dtype,
        layernorm_eps=config["rms_norm_eps"],
        prefill_flash=prefill_flash, decode_attention=decode_attention,
        head_dim=d, num_kv_heads=config["num_key_value_heads"],
        family="rope_moe", layers=(spec,) * n,
        norm_unit_offset=config["norm_add_unit_offset"],
        pred_heads=config["num_pred_heads"])


def make_weights(config: dict, cfg, seed: int):
    from apex_tpu.serving import init_rope_moe_weights

    return init_rope_moe_weights(
        jax.random.PRNGKey(fold_seed(seed)), cfg,
        dense_ffn=config["intermediate_size"], std=config["init_std"])


def evabyte(config: dict, traffic: dict, seed: int) -> ServeJob:
    from apex_tpu.amp import get_policy
    from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                                  default_cache_config)

    if not traffic["kind"].startswith("serve_open_loop"):
        raise ValueError(f"evabyte builder: no kind {traffic['kind']!r}")
    eng = traffic["engine"]
    dtype = get_policy(eng["policy"]).cast_model_type or jnp.float32
    cfg = serving_config(
        config, max_seq=traffic["max_total_tokens"], dtype=dtype,
        prefill_flash=eng["prefill_flash"],
        decode_attention=eng["decode_attention"])
    weights = make_weights(config, cfg, seed)
    cache_cfg = default_cache_config(
        cfg, num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        kv_dtype=eng["kv_dtype"])
    engine = ServingEngine(
        weights, cfg, cache_cfg,
        ladder=BucketLadder(batch=tuple(eng["batch_rungs"]),
                            pages=tuple(eng["page_rungs"]),
                            chunks=tuple(eng["chunk_rungs"])),
        monitor=None, autoresume=None, snapshot=None,
        speculate_k=eng["speculate_k"],
        prefill_chunk=eng["prefill_chunk"], prefix_share=False, slo=None,
        clock=time.perf_counter)
    margins = jax.jit(functools.partial(reference_evabyte.margins,
                                        config=config))
    heads = config["num_attention_heads"]
    # what this cell's rooflines count from: the model's shapes, and
    # beside them the engine's own sums over the decode ticks (and the
    # prefill chunks in their spans) that ran while a profiler session
    # was on, added in place as they run
    engine.tick_sums.update(
        heads=heads, head_dim=config["hidden_size"] // heads,
        layers=config["num_hidden_layers"], block_size=eng["block_size"])
    return ServeJob(
        engine=engine,
        make_request=lambda rid, prompt, n: Request(
            rid=rid, prompt=prompt, max_new_tokens=n),
        vocab=config["vocab_size"],
        reference_margins=lambda tokens, emitted: margins(
            weights, tokens, emitted),
        facts={"decode_geometry": dict(
                   block_size=eng["block_size"], heads=heads,
                   head_dim=config["hidden_size"] // heads,
                   layers=config["num_hidden_layers"]),
               "tick_sums": engine.tick_sums})
