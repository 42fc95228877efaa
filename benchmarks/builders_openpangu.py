"""The builder of the ``openpangu-ultra-moe`` configuration: from its
published ``config.json`` keys, its ``deployment_share`` (what one chip
of the sixteen that share a layer holds) and a ``serve_open_loop``
traffic file to the program's own serving engine (``apex_tpu.serving``),
the third family of its model (``mla_moe``), with seeded random weights
made on the device in one jitted call and ``reference_openpangu`` as the
plain reference.  The traffic's ``engine.speculate_k`` 1 serves the
model's own multi-token-prediction module as the engine's draft; 0
leaves the module off (no weights, no cache layer for it).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from . import reference_openpangu
from .builders import ServeJob, fold_seed


def serving_config(config: dict, *, max_seq: int, dtype,
                   prefill_flash: bool = True,
                   decode_attention: str = "kernel", mtp: bool = False):
    """The published keys as the program's ``ServingModelConfig``."""
    from apex_tpu.serving import (LayerSpec, MlaSpec, RopeSpec,
                                  ServingModelConfig)

    share = config["deployment_share"]
    if share["experts_held"] != config["n_routed_experts"]:
        raise ValueError("n_routed_experts is the experts held here")
    rope = RopeSpec(theta=float(config["rope_theta"]),
                    rotary_dim=config["qk_rope_head_dim"])
    n = config["num_hidden_layers"]
    return ServingModelConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"], num_layers=n,
        max_seq=max_seq, dtype=dtype,
        layernorm_eps=config["rms_norm_eps"],
        prefill_flash=prefill_flash, decode_attention=decode_attention,
        num_experts=share["router_outputs"], family="mla_moe",
        layers=tuple(
            LayerSpec(num_heads=config["num_attention_heads"],
                      window=None, rope=rope,
                      moe=i >= config["first_k_dense_replace"])
            for i in range(n)),
        experts_per_token=config["num_experts_per_tok"],
        routed_scaling=config["routed_scaling_factor"],
        expert_first=share["expert_first"],
        mla=MlaSpec(q_rank=config["q_lora_rank"],
                    kv_rank=config["kv_lora_rank"],
                    nope_dim=config["qk_nope_head_dim"],
                    rope_dim=config["qk_rope_head_dim"],
                    v_dim=config["v_head_dim"]),
        mtp_layers=int(mtp))


def make_weights(config: dict, cfg, seed: int):
    from apex_tpu.serving import init_mla_moe_weights

    return init_mla_moe_weights(
        jax.random.PRNGKey(fold_seed(seed)), cfg,
        dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared_ffn=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        experts_held=config["n_routed_experts"], mtp=cfg.mtp_layers > 0)


def openpangu(config: dict, traffic: dict, seed: int) -> ServeJob:
    from apex_tpu.amp import get_policy
    from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                                  default_cache_config)

    if not traffic["kind"].startswith("serve_open_loop"):
        raise ValueError(f"openpangu builder: no kind {traffic['kind']!r}")
    eng = traffic["engine"]
    dtype = get_policy(eng["policy"]).cast_model_type or jnp.float32
    speculate_k = eng.get("speculate_k", 0)
    cfg = serving_config(
        config, max_seq=max(eng["page_rungs"]) * eng["block_size"],
        dtype=dtype, prefill_flash=eng["prefill_flash"],
        decode_attention=eng["decode_attention"], mtp=speculate_k > 0)
    weights = make_weights(config, cfg, seed)
    cache_cfg = default_cache_config(
        cfg, num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        kv_dtype=eng["kv_dtype"])
    engine = ServingEngine(
        weights, cfg, cache_cfg,
        ladder=BucketLadder(batch=tuple(eng["batch_rungs"]),
                            pages=tuple(eng["page_rungs"])),
        monitor=None, autoresume=None, snapshot=None,
        speculate_k=speculate_k, spec_governor=None, prefill_chunk=0,
        prefix_share=False, slo=None, clock=time.perf_counter)
    margins = jax.jit(functools.partial(reference_openpangu.margins,
                                        config=config))
    mla = cfg.mla
    # what this cell's rooflines count from: the model's shapes, and
    # beside them the engine's own sums over the decode ticks that ran
    # while a profiler session was on, added in place as they run
    engine.tick_sums.update(
        hidden=config["hidden_size"],
        expert_width=config["moe_intermediate_size"],
        heads=config["num_attention_heads"], latent_dim=mla.latent_dim,
        value_dim=mla.kv_rank, qk_dim=mla.nope_dim + mla.rope_dim,
        v_dim=mla.v_dim, layers=config["num_hidden_layers"],
        block_size=eng["block_size"])
    return ServeJob(
        engine=engine,
        make_request=lambda rid, prompt, n: Request(
            rid=rid, prompt=prompt, max_new_tokens=n),
        vocab=config["vocab_size"],
        reference_margins=lambda tokens, emitted: margins(
            weights, tokens, emitted),
        facts={"decode_geometry": dict(
                   block_size=eng["block_size"], heads=1,
                   head_dim=mla.latent_dim,
                   layers=config["num_hidden_layers"]),
               "tick_sums": engine.tick_sums})
