"""The builder of the ``laguna-xs2`` configuration: from its published
``config.json`` keys and a ``serve_open_loop`` traffic file to the
program's own serving engine (``apex_tpu.serving``), the second family
of its model (``rope_moe``), with seeded random weights made on the
device in one jitted call and ``reference_laguna`` as the plain
reference.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from . import reference_laguna
from .builders import ServeJob, fold_seed


def serving_config(config: dict, *, max_seq: int, dtype,
                   prefill_flash: bool = True,
                   decode_attention: str = "kernel"):
    """The published keys as the program's ``ServingModelConfig``."""
    from apex_tpu.serving import LayerSpec, RopeSpec, ServingModelConfig

    d = config["head_dim"]

    def rope(kind):
        p = config["rope_parameters"][kind]
        spec = dict(theta=float(p["rope_theta"]),
                    rotary_dim=int(p["partial_rotary_factor"] * d))
        if p["rope_type"] == "yarn":
            spec.update(
                yarn_factor=float(p["factor"]),
                original_max_position=p[
                    "original_max_position_embeddings"],
                beta_fast=float(p["beta_fast"]),
                beta_slow=float(p["beta_slow"]),
                attention_factor=float(p["attention_factor"]))
        return RopeSpec(**spec)

    n = config["num_hidden_layers"]
    layers = tuple(
        LayerSpec(num_heads=config["num_attention_heads_per_layer"][i],
                  window=config["sliding_window"]
                  if config["layer_types"][i] == "sliding_attention"
                  else None,
                  rope=rope(config["layer_types"][i]),
                  moe=config["mlp_layer_types"][i] == "sparse")
        for i in range(n))
    return ServingModelConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=n,
        max_seq=max_seq, dtype=dtype,
        layernorm_eps=config["rms_norm_eps"],
        prefill_flash=prefill_flash, decode_attention=decode_attention,
        num_experts=config["num_experts"], head_dim=d,
        num_kv_heads=config["num_key_value_heads"], family="rope_moe",
        layers=layers, experts_per_token=config["num_experts_per_tok"],
        routed_scaling=config["moe_routed_scaling_factor"])


def make_weights(config: dict, cfg, seed: int):
    from apex_tpu.serving import init_rope_moe_weights

    return init_rope_moe_weights(
        jax.random.PRNGKey(fold_seed(seed)), cfg,
        dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared_ffn=config["shared_expert_intermediate_size"])


def laguna(config: dict, traffic: dict, seed: int) -> ServeJob:
    from apex_tpu.amp import get_policy
    from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                                  default_cache_config)

    if not traffic["kind"].startswith("serve_open_loop"):
        raise ValueError(f"laguna builder: no kind {traffic['kind']!r}")
    eng = traffic["engine"]
    dtype = get_policy(eng["policy"]).cast_model_type or jnp.float32
    cfg = serving_config(
        config, max_seq=max(eng["page_rungs"]) * eng["block_size"],
        dtype=dtype, prefill_flash=eng["prefill_flash"],
        decode_attention=eng["decode_attention"])
    weights = make_weights(config, cfg, seed)
    cache_cfg = default_cache_config(
        cfg, num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        kv_dtype=eng["kv_dtype"])
    engine = ServingEngine(
        weights, cfg, cache_cfg,
        ladder=BucketLadder(batch=tuple(eng["batch_rungs"]),
                            pages=tuple(eng["page_rungs"])),
        monitor=None, autoresume=None, snapshot=None, speculate_k=0,
        prefill_chunk=0, prefix_share=False, slo=None,
        clock=time.perf_counter)
    margins = jax.jit(functools.partial(reference_laguna.margins,
                                        config=config))
    kinds = config["layer_types"]
    heads = config["num_attention_heads_per_layer"]
    # what the two rooflines of this cell count from: the model's
    # shapes, and beside them the engine's own sums over the decode
    # ticks that ran while a profiler session was on, which the engine
    # adds to its dict in place as they run
    engine.tick_sums.update(
        hidden=config["hidden_size"],
        expert_width=config["moe_intermediate_size"],
        experts_per_token=config["num_experts_per_tok"],
        moe_layers=config["mlp_layer_types"].count("sparse"),
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], block_size=eng["block_size"],
        full_layers=kinds.count("full_attention"),
        window_layers=kinds.count("sliding_attention"),
        full_heads=heads[kinds.index("full_attention")],
        window_heads=heads[kinds.index("sliding_attention")])
    return ServeJob(
        engine=engine,
        make_request=lambda rid, prompt, n: Request(
            rid=rid, prompt=prompt, max_new_tokens=n),
        vocab=config["vocab_size"],
        # the kinds take (margins, spreads); the router's score gaps
        # are for the CPU tests (tests/test_serving_rope_moe.py)
        reference_margins=lambda tokens, emitted: margins(
            weights, tokens, emitted)[:2],
        facts={"decode_geometry": dict(
                   block_size=eng["block_size"],
                   heads=config["num_key_value_heads"],
                   head_dim=config["head_dim"], layers=len(kinds)),
               "tick_sums": engine.tick_sums})
