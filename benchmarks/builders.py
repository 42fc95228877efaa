"""From a configuration file and a traffic file to the system under
test: the ``builder`` a configuration names (``module:function``) is
called as ``builder(config, traffic, seed)`` and returns a
:class:`TrainJob` or a :class:`ServeJob` for the traffic's ``kind``.

Everything here is the program's own entry points (``apex_tpu``) called
the way ``chip_smoke.py`` calls them; the benchmark adds only sizes,
the seed and its references.  Weights are made on the device from the
seed in one jitted call; nothing is read from disk.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import reference


@dataclasses.dataclass
class TrainJob:
    step: Callable            # jitted: (params, amp_state) -> (params,
    #                           amp_state, loss, grad_norm, info)
    params: Any
    amp_state: Any
    tokens_per_step: int
    n_params: int
    flops_per_token: float    # forward + backward, for the MFU line
    reference_loss: Callable[[], float]   # on the initial weights
    facts: Dict[str, Any]     # shapes for the roofline readers


@dataclasses.dataclass
class ServeJob:
    engine: Any               # apex_tpu.serving.ServingEngine
    make_request: Callable    # (rid, prompt, max_new_tokens) -> Request
    vocab: int                # real token ids: [0, vocab)
    reference_margins: Callable   # (tokens, emitted) (b, s) ->
    #                               reference.gpt_margins' pair
    facts: Dict[str, Any]


def fold_seed(seed: int) -> int:
    """--seed goes a little over 2**31; a jax PRNG key takes 32 signed
    bits."""
    return int(seed) % (2 ** 31 - 1)


def _dense_flops_per_token(n_matmul_params: int, layers: int, seq: int,
                           hidden: int, causal: bool) -> float:
    """Forward + backward flops a token: 6 per matmul parameter, and
    attention's two score-sized matmuls (forward 2, backward 4, at 2
    flops a multiply-add), a causal mask counted once."""
    pairs = (seq + 1) / 2 if causal else seq
    return 6.0 * n_matmul_params + 12.0 * layers * pairs * hidden


def _count(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


def _matmul_params(params, tied_head_rows: int, hidden: int) -> int:
    """Parameters that multiply every token: all 2-D kernels, and the
    tied output embedding once (the input look-up is no matmul, the
    position table none either)."""
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 2 and "embeddings" not in name:
            total += leaf.size
    return total + tied_head_rows * hidden


def _amp_train_setup(init_fn, tx, opt_level):
    """``init_fn(key) -> params`` and ``amp.initialize`` in ONE jitted
    call: weights, fp32 masters and optimizer state are made on the
    device from the seed.  The amp optimizer object (no arrays) is
    handed out of the trace."""
    from apex_tpu import amp

    holder = {}

    def make(key):
        params, amp_opt, amp_state = amp.initialize(
            init_fn(key), tx, opt_level=opt_level)
        holder["amp_opt"] = amp_opt
        return params, amp_state

    return make, holder


# --- GPT ------------------------------------------------------------------------

def _gpt_sizes(config: dict) -> dict:
    return dict(vocab=config["assumed"]["padded_vocab_size"],
                hidden=config["n_embd"], heads=config["n_head"],
                layers=config["n_layer"], ffn=config["n_inner"],
                positions=config["n_positions"])


def gpt(config: dict, traffic: dict, seed: int):
    if traffic["kind"] == "train":
        return _gpt_train(config, traffic, seed)
    if traffic["kind"] == "serve_open_loop":
        return _gpt_serve(config, traffic, seed)
    raise ValueError(f"gpt builder: no kind {traffic['kind']!r}")


def _gpt_model(sz: dict, seq: int, *, use_flash: bool, dtype):
    from apex_tpu.testing.standalone_gpt import GPTModel

    return GPTModel(
        vocab_size=sz["vocab"], hidden_size=sz["hidden"],
        num_layers=sz["layers"], num_attention_heads=sz["heads"],
        max_sequence_length=seq, ffn_hidden_size=sz["ffn"],
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=use_flash,
        dtype=dtype)


def _gpt_train(config, traffic, seed) -> TrainJob:
    from apex_tpu import optimizers
    from apex_tpu.testing.standalone_gpt import (SmokeSetup,
                                                 build_train_step)

    sz = _gpt_sizes(config)
    batch, seq = traffic["batch"], traffic["sequence"]
    model = _gpt_model(sz, seq, use_flash=True, dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(fold_seed(seed))
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (batch, seq),
                                0, config["vocab_size"])
    labels = jnp.roll(tokens, -1, -1)
    # the parameters do not depend on the attention route or the batch:
    # initialise through the dense twin on one short row, so set-up
    # lowers no kernel it will not run
    twin = _gpt_model(sz, seq, use_flash=False, dtype=jnp.bfloat16)
    make, holder = _amp_train_setup(
        lambda k: twin.init(k, tokens[:1, :8])["params"],
        getattr(optimizers, traffic["optimizer"])(traffic["lr"]),
        traffic["opt_level"])
    params, amp_state = jax.jit(make)(key)
    n_params = _count(params)
    setup = SmokeSetup(model, tokens, labels, params, holder["amp_opt"],
                       amp_state, n_params)

    def reference_loss():
        total, = reference.mean_loss_in_chunks(
            functools.partial(reference.gpt_loss_sum, heads=sz["heads"]),
            params, (tokens, labels), chunk=min(2, batch))
        return total / tokens.size

    return TrainJob(
        step=build_train_step(setup), params=params, amp_state=amp_state,
        tokens_per_step=batch * seq, n_params=n_params,
        flops_per_token=_dense_flops_per_token(
            _matmul_params(params, sz["vocab"], sz["hidden"]),
            sz["layers"], seq, sz["hidden"], causal=True),
        reference_loss=reference_loss,
        facts={"attention_shapes": dict(
            batch=batch, seq=seq, heads=sz["heads"],
            head_dim=sz["hidden"] // sz["heads"], layers=sz["layers"],
            causal=True)})


def _gpt_serve(config, traffic, seed) -> ServeJob:
    import time

    from apex_tpu.amp import get_policy
    from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                                  ServingModelConfig,
                                  default_cache_config,
                                  extract_serving_weights)

    sz = _gpt_sizes(config)
    eng = traffic["engine"]
    dtype = get_policy(eng["policy"]).cast_model_type or jnp.float32
    model = _gpt_model(sz, sz["positions"], use_flash=False, dtype=dtype)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(fold_seed(seed)),
        jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = ServingModelConfig.from_model(
        model, prefill_flash=eng["prefill_flash"],
        decode_attention=eng["decode_attention"])
    cache_cfg = default_cache_config(
        cfg, num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        kv_dtype=eng["kv_dtype"])
    engine = ServingEngine(
        extract_serving_weights(params, sz["layers"]), cfg, cache_cfg,
        ladder=BucketLadder(batch=tuple(eng["batch_rungs"]),
                            pages=tuple(eng["page_rungs"])),
        monitor=None, autoresume=None, snapshot=None, speculate_k=0,
        prefill_chunk=0, prefix_share=False, slo=None,
        clock=time.perf_counter)
    heads = sz["heads"]
    return ServeJob(
        engine=engine,
        make_request=lambda rid, prompt, n: Request(
            rid=rid, prompt=prompt, max_new_tokens=n),
        vocab=config["vocab_size"],
        reference_margins=functools.partial(jax.jit(functools.partial(
            reference.gpt_margins, heads=heads)), params),
        facts={"decode_geometry": dict(
            block_size=eng["block_size"], heads=heads,
            head_dim=sz["hidden"] // heads, layers=sz["layers"])})


# --- BERT -----------------------------------------------------------------------

def bert(config: dict, traffic: dict, seed: int) -> TrainJob:
    from apex_tpu import optimizers
    from apex_tpu.testing.standalone_bert import (BertModel,
                                                  BertSmokeSetup,
                                                  build_train_step)

    if traffic["kind"] != "train":
        raise ValueError(f"bert builder: no kind {traffic['kind']!r}")
    vocab = config["assumed"]["padded_vocab_size"]
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    layers = config["num_hidden_layers"]
    batch, seq = traffic["batch"], traffic["sequence"]

    def bert_model(use_flash):
        # ffn is 4 x hidden in the program's block: intermediate_size
        # of the published config is exactly that
        return BertModel(
            vocab_size=vocab, hidden_size=hidden, num_layers=layers,
            num_attention_heads=heads, max_sequence_length=seq,
            attention_dropout=0.0, hidden_dropout=0.0,
            use_flash=use_flash, dtype=jnp.bfloat16)

    if config["intermediate_size"] != 4 * hidden:
        raise ValueError("BertModel's feed-forward width is 4 x hidden")
    key = jax.random.PRNGKey(fold_seed(seed))
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (batch, seq),
                                0, config["vocab_size"])
    mask = jnp.ones((batch, seq), jnp.int32)
    labels = jnp.roll(tokens, -1, -1)
    nsp = jax.random.randint(jax.random.fold_in(key, 2), (batch,), 0, 2)
    twin = bert_model(use_flash=False)
    make, holder = _amp_train_setup(
        lambda k: twin.init(k, tokens[:1, :8], mask[:1, :8])["params"],
        getattr(optimizers, traffic["optimizer"])(traffic["lr"]),
        traffic["opt_level"])
    params, amp_state = jax.jit(make)(key)
    n_params = _count(params)
    setup = BertSmokeSetup(bert_model(use_flash=True), tokens, mask,
                           labels, nsp, params, holder["amp_opt"],
                           amp_state, n_params)

    def reference_loss():
        lm, ns = reference.mean_loss_in_chunks(
            functools.partial(reference.bert_loss_sums, heads=heads),
            params, (tokens, labels, nsp), chunk=min(2, batch))
        return lm / tokens.size + ns / batch

    return TrainJob(
        step=build_train_step(setup), params=params, amp_state=amp_state,
        tokens_per_step=batch * seq, n_params=n_params,
        flops_per_token=_dense_flops_per_token(
            _matmul_params(params, vocab, hidden), layers, seq, hidden,
            causal=False),
        reference_loss=reference_loss,
        facts={"attention_shapes": dict(
            batch=batch, seq=seq, heads=heads, head_dim=hidden // heads,
            layers=layers, causal=False)})
