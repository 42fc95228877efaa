#!/usr/bin/env python3
"""The quickest proof that apex_tpu still starts on the chip.

One process, one TPU v5e chip: GPT-2 345M takes six train steps through
``train_smoke`` (O5: bf16 compute, fp32 masters, FusedAdam, flash
attention and LayerNorm as Mosaic kernels) and answers eight requests
through ``serve_smoke`` (paged KV cache, flash prefill, the flash-decode
kernel), then the two attention kernels are held against their dense
references on the chip at the same shapes.  Any phase that fails raises,
and the run ends non-zero; the last line of standard output is the
driver's JSON object and is printed only when every phase passed.

``--multichip`` runs one other thing and nothing else: the tensor x data
parallel ``gpt_forward_pipelined`` train step on four chips, against the
same function on one of them.

Every phase is a function of its sizes, so the CPU tests call the same
code at ``hidden=64, layers=2`` with the kernels in interpret mode.
``main()`` itself always demands the chip.  The timings printed are
facts of this one run — set-up included, nothing repeated — not
benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import statistics
import sys
import time
from importlib import metadata

GPT2_345M = dict(vocab=50304, hidden=1024, num_heads=16, num_layers=24)
TRAIN = dict(batch=8, seq=1024, steps=6)
# prompts of 16-900 tokens (serve_smoke draws them from the seed; the
# phase checks the span), 32 new tokens, a ladder spanning max_seq
SERVE = dict(max_seq=1024, block_size=16, batch_rungs=(1, 2, 4, 8),
             page_rungs=(16, 64), num_requests=8, max_new_tokens=32,
             prompt_span=(16, 900), seed=85)
# kernels_in_step: inside shard_map's manual axes every fused op takes
# its jnp twin today (ROADMAP S7), so the 2x2 step holds no Mosaic
# kernel; the phase fails when that changes without this line changing
MULTICHIP = dict(batch=8, seq=1024, dp=2, tp=2, kernels_in_step=0)


def say(phase: str, **facts) -> None:
    print(f"[chip_smoke] {phase}: "
          + " ".join(f"{k}={v}" for k, v in facts.items()), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def result_line(devices) -> str:
    """The driver's last line, with the device as jax reports it."""
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind, "count": len(devices)}})


def peak_bytes(device):
    """Process-lifetime peak, so a later phase shows an earlier one's
    (the CPU keeps no such count: None there)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def count_kernels(text: str, device, what: str) -> int:
    """Mosaic kernels in a compiled program's text.  On a TPU a program
    without one fell back to the jnp twins, and the run refuses it; off
    the TPU the kernels run interpreted and the count is 0."""
    n = text.count("tpu_custom_call")
    check(n > 0 or device.platform != "tpu",
          f"{what} holds no tpu_custom_call: the Pallas kernels fell "
          f"back to their jnp twins")
    return n


# --- device ---------------------------------------------------------------

def device_phase(devices) -> None:
    import jax
    import jaxlib

    from apex_tpu.pyprof.prof import device_spec

    d = devices[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:   # a fact to print, not a gate
        libtpu = "unknown"
    # raises on an accelerator with no peak-rate row: an MFU against
    # another chip's peak would be wrong, so the run stops here
    spec = device_spec(d)
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devices), jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu,
        spec=f"{spec.name}/{spec.peak_bf16_tflops}TFLOPs/"
             f"{spec.peak_hbm_gbps}GBps")


# --- train ----------------------------------------------------------------

def train_phase(device, *, vocab, hidden, num_heads, num_layers, batch,
                seq, steps) -> dict:
    """``steps`` O5/bf16/flash train steps through ``train_smoke`` on a
    fixed batch on ``device`` (jax's default); the step program's text
    is taken from the same builder the loop uses, lowered once
    beforehand (with the persistent cache on, the loop's own compile of
    that program is then a hit)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.monitor import MemorySink
    from apex_tpu.ops import flash_attention
    from apex_tpu.testing.standalone_gpt import (build_train_step,
                                                 make_smoke_setup,
                                                 train_smoke)

    model = dict(vocab=vocab, hidden=hidden, num_heads=num_heads,
                 num_layers=num_layers, batch=batch, seq=seq,
                 opt_level="O5", dtype=jnp.bfloat16, use_flash=True)
    flash_attention._E_FALLBACK_SEEN.clear()

    setup = make_smoke_setup(**model)
    t0 = time.perf_counter()
    compiled = build_train_step(setup).lower(
        setup.params, setup.amp_state).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = count_kernels(compiled.as_text(), device, "train step")
    n_params = setup.n_params
    del setup, compiled
    gc.collect()

    sink = MemorySink()
    loss, params, amp_state, done = train_smoke(
        steps, sink=sink, return_state=True, **model)
    check(done == steps, f"{done} of {steps} steps ran")
    series = {name: [e.value for e in sink.events
                     if e.kind == "metric" and e.name == name]
              for name in ("loss", "step_ms")}
    losses, step_ms = series["loss"], series["step_ms"]
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(l is not None and math.isfinite(l) for l in losses),
          f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    weights = [p for p in jax.tree.leaves(params) if p.ndim >= 2]
    check(all(p.dtype == jnp.bfloat16 for p in weights),
          "O5 params are not bf16")
    masters = jax.tree.leaves(amp_state.master_params)
    check(masters and all(m.dtype == jnp.float32 for m in masters),
          "O5 masters are not fp32")
    check(not flash_attention._E_FALLBACK_SEEN,
          f"flash_attention_e fell back: "
          f"{sorted(flash_attention._E_FALLBACK_SEEN)}")
    facts = dict(
        params=n_params, losses=[round(l, 4) for l in losses],
        lower_and_compile_s=round(compile_s, 1),
        first_step_ms=round(step_ms[0], 1),
        step_ms_median_after_first=round(
            statistics.median(step_ms[1:]), 2),
        kernels_in_step=n_kernels,
        peak_bytes_in_use=peak_bytes(device))
    say("train", **facts)
    return facts


# --- serve ----------------------------------------------------------------

def _serve(decode_attention, *, vocab, hidden, num_heads, num_layers,
           max_seq, block_size, batch_rungs, page_rungs, num_requests,
           max_new_tokens, seed):
    from apex_tpu.serving import BucketLadder
    from apex_tpu.testing.standalone_gpt import serve_smoke

    return serve_smoke(
        num_requests, vocab=vocab, hidden=hidden, num_heads=num_heads,
        num_layers=num_layers, max_seq=max_seq,
        max_new_tokens=max_new_tokens, seed=seed, policy="O5",
        decode_attention=decode_attention, prefill_flash=True,
        num_blocks=batch_rungs[-1] * page_rungs[-1] + 1,
        block_size=block_size,
        ladder=BucketLadder(batch=batch_rungs, pages=page_rungs),
        sanitize=True, return_engine=True)


def serve_phase(device, *, prompt_span, **sizes) -> dict:
    """Serve through ``serve_smoke`` with the decode kernel on
    ``device`` (jax's default), under ``sanitize`` (a post-warmup
    recompile raises), then once more with the dense reference decode
    to count the tokens the two agree on."""
    batch_rungs, page_rungs = sizes["batch_rungs"], sizes["page_rungs"]
    n, new = sizes["num_requests"], sizes["max_new_tokens"]

    summary, engine = _serve("kernel", **sizes)
    check(summary.requests_done == n and summary.requests_preempted == 0,
          f"{summary.requests_done} of {n} requests finished")
    tokens = {r.rid: list(r.out_tokens) for r in engine.done}
    check(all(len(t) == new for t in tokens.values()),
          "a request came back short")
    lengths = sorted(len(r.prompt) for r in engine.done)
    check(prompt_span[0] <= lengths[0] and lengths[-1] <= prompt_span[1],
          f"prompt lengths {lengths} outside {prompt_span}")
    programs = len(page_rungs) * (len(batch_rungs) + 1)
    check(sorted(summary.compiles.values()) == [1] * programs,
          f"expected {programs} programs compiled once each, got "
          f"{summary.compiles}")
    check(summary.tokens_per_sec > 0, "tokens_per_sec is not positive")
    n_kernels = count_kernels(
        engine._decode_fn(batch_rungs[-1], page_rungs[-1]).as_text(),
        device, "decode step")
    facts = dict(
        requests_done=summary.requests_done, prompt_lengths=lengths,
        tokens_generated=summary.tokens_generated,
        programs_compiled=programs,
        post_warmup_recompiles=sum(summary.compiles.values()) - programs,
        tokens_per_sec=round(summary.tokens_per_sec, 1),
        decode_tokens_per_sec=round(summary.decode_tokens_per_sec, 1),
        ttft_p50_ms=summary.ttft_p50_ms, itl_p50_ms=summary.itl_p50_ms,
        kernels_in_decode_step=n_kernels,
        peak_bytes_in_use=peak_bytes(device))
    del engine
    gc.collect()

    # token streams do not depend on the bucket shape, so the reference
    # engine gets a one-rung ladder: two programs to compile, not ten
    _, ref_engine = _serve("reference", **dict(
        sizes, batch_rungs=batch_rungs[-1:], page_rungs=page_rungs[-1:]))
    ref = {r.rid: list(r.out_tokens) for r in ref_engine.done}
    same = sum(a == b for rid in tokens
               for a, b in zip(tokens[rid], ref.get(rid, ())))
    # a number to read, not a gate: random bf16 weights tie often
    facts["tokens_matching_reference_decode"] = f"{same}/{n * new}"
    say("serve", **facts)
    return facts


# --- kernels against their references --------------------------------------

def parity_phase(*, num_heads, head_dim, batch, seq, block_size,
                 pages) -> dict:
    """flash_decode vs the dense paged gather, flash_attention_e vs the
    dense softmax, at the serving and training shapes, bf16 tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops.flash_attention import (flash_attention_e,
                                              mha_reference)
    from apex_tpu.ops.flash_decode import (flash_decode,
                                           pack_decode_heads,
                                           paged_attention_reference,
                                           use_decode_head_packing)

    h, d, bs = num_heads, head_dim, block_size
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    nb = batch * pages + 1
    q = jax.random.normal(keys[0], (batch, h, d), jnp.bfloat16)
    kv = jax.random.normal(keys[1], (2, nb, h, bs, d), jnp.bfloat16)
    if use_decode_head_packing(h, d):     # the cache manager's layout
        kv = pack_decode_heads(
            kv.transpose(0, 1, 3, 2, 4)).transpose(0, 1, 3, 2, 4)
    tables = jnp.asarray(
        np.random.RandomState(0).permutation(nb - 1)[:batch * pages]
        .reshape(batch, pages) + 1, jnp.int32)
    span = pages * bs
    seq_lens = jnp.asarray(
        [0, 1, bs, span] + [span * (i + 1) // (batch + 1)
                            for i in range(batch - 4)], jnp.int32)[:batch]
    args = (q, kv[0], kv[1], tables, seq_lens)
    got = jax.jit(flash_decode)(*args).astype(jnp.float32)
    want = jax.jit(paged_attention_reference)(*args).astype(jnp.float32)
    decode_err = float(jnp.max(jnp.abs(got - want)))
    check(bool(jnp.all(jnp.isfinite(got))), "flash_decode not finite")
    check(decode_err < 3e-2,
          f"flash_decode off its reference by {decode_err}")
    check(float(jnp.max(jnp.abs(got[0]))) == 0.0,
          "flash_decode row with seq_len 0 is not exactly 0")

    qkv = jax.random.normal(keys[2], (batch, seq, h, 3 * d), jnp.bfloat16)
    got = jax.jit(lambda x: flash_attention_e(x, causal=True))(qkv)

    def dense(x):
        q_, k_, v_ = (t.transpose(0, 2, 1, 3)
                      for t in jnp.split(x, 3, axis=-1))
        out = mha_reference(q_, k_, v_, causal=True)
        return out.transpose(0, 2, 1, 3).reshape(batch, seq, h * d)

    want = jax.jit(dense)(qkv)
    e_err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                  - want.astype(jnp.float32))))
    check(bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))),
          "flash_attention_e not finite")
    check(e_err < 3e-2,
          f"flash_attention_e off its reference by {e_err}")
    facts = dict(flash_decode_max_abs_err=round(decode_err, 5),
                 flash_attention_e_max_abs_err=round(e_err, 5))
    say("parity", **facts)
    return facts


# --- four chips ------------------------------------------------------------

def multichip_phase(devices, *, vocab, hidden, num_heads, num_layers,
                    batch, seq, dp, tp, kernels_in_step) -> dict:
    """Two tensor x data parallel train steps of the
    ``gpt_forward_pipelined`` program on ``dp * tp`` devices — one
    compiled executable, fed its own outputs — against the forward loss
    of the same function on one device from the same host-held
    params."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import __graft_entry__ as graft

    check(len(devices) >= dp * tp,
          f"{dp}x{tp} mesh needs {dp * tp} devices, have {len(devices)}")
    sizes = dict(vocab=vocab, hidden=hidden, num_heads=num_heads,
                 seq=seq, layers_per_stage=num_layers,
                 dtype=jnp.bfloat16, use_flash=True)
    wide = graft.build_gpt_3d(devices[:dp * tp], tp=tp, pp=1, **sizes)
    # Initialised once and parked on the host; both meshes are fed from
    # that copy.  (The init itself runs on the default device: flax runs
    # the forward to make the params, and the Pallas kernels in it pick
    # interpret mode from the default backend, not from where they run:
    # ROADMAP S7.)
    host_copy = jax.tree.map(np.asarray, (
        jax.jit(wide.init)(jax.random.PRNGKey(0)),
        *wide.batch(jax.random.PRNGKey(1), batch)))

    params, tokens, labels = wide.place(*host_copy)
    opt_state = wide.init_opt(params)
    compiled = wide.train_step.lower(params, opt_state, tokens,
                                     labels).compile()
    text = compiled.as_text()
    n_kernels = text.count("tpu_custom_call")
    check(n_kernels == kernels_in_step,
          f"{n_kernels} Mosaic kernels in the {dp}x{tp} step, expected "
          f"{kernels_in_step}")
    # the executable takes its arguments in one layout only, so the
    # second call proves that a step's outputs are its next inputs
    losses = []
    for _ in range(2):
        params, opt_state, loss = compiled(params, opt_state, tokens,
                                           labels)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[1] < losses[0], f"loss did not fall: {losses}")

    # the split is real: after two steps the tensor-parallel leaves
    # still hold 1/tp of their elements on each device, the batch 1/dp,
    # and nothing that should be split sits whole on device 0
    mesh_axes = dict(zip(wide.mesh.axis_names, wide.mesh.devices.shape))
    split = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        for key in ("word_embeddings", "query_key_value",
                    "dense_h_to_4h"):
            if key in name and leaf.ndim >= 2:
                shard = leaf.addressable_shards[0].data.shape
                check(not leaf.sharding.is_fully_replicated
                      and int(np.prod(shard)) * tp == leaf.size,
                      f"{name}: device shard {shard} of {leaf.shape} "
                      f"is not 1/{tp} ({leaf.sharding})")
                split[key] = split.get(key, 0) + 1
    check(set(split) == {"word_embeddings", "query_key_value",
                         "dense_h_to_4h"},
          f"did not find every tensor-split leaf: {split}")

    # ... and Adam's moments with them, element for element
    def held_by_first(tree):
        return sum(x.addressable_shards[0].data.size
                   for x in jax.tree.leaves(tree))

    held = [held_by_first(t) for t in (params, opt_state.m, opt_state.v)]
    check(held[0] == held[1] == held[2],
          f"device 0 holds {held} elements of params, m, v: the "
          f"optimizer state is not split like the params")
    state_share = held[1] / sum(x.size for x in opt_state.m)
    check(tokens.addressable_shards[0].data.shape[0] * dp == batch,
          "batch is not split over data")
    check(len({s.device for s in tokens.addressable_shards}) == dp * tp,
          "batch does not reach every device")
    n_all_reduce = text.count("all-reduce(") + text.count(
        "all-reduce-start(")
    check(n_all_reduce > 0, "no all-reduce in the compiled step")
    mem = compiled.memory_analysis()
    del params, opt_state, compiled, text
    gc.collect()

    one = graft.build_gpt_3d(devices[:1], tp=1, pp=1, **sizes)
    p1, t1, l1 = one.place(*host_copy)
    loss_one = float(jax.jit(one.loss)(p1, t1, l1))
    check(abs(loss_one - losses[0]) < 5e-2,
          f"step-1 loss {losses[0]} on {dp}x{tp} vs {loss_one} on 1x1")
    facts = dict(
        mesh=mesh_axes, losses=[round(l, 4) for l in losses],
        loss_1x1=round(loss_one, 4),
        tensor_split_leaves=split, all_reduces_in_step=n_all_reduce,
        share_of_adam_state_on_device_0=round(state_share, 4),
        kernels_in_step=n_kernels,
        temp_bytes_per_device=getattr(mem, "temp_size_in_bytes", None),
        peak_bytes_in_use=peak_bytes(devices[0]))
    say("multichip", **facts)
    return facts


# --- entry ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip tensor x data parallel "
                         "train step and its one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"[chip_smoke] no TPU: jax found {len(devices)} "
              f"{d.platform} device(s) ({d.device_kind}); this script "
              f"proves the chip path and does not fall back",
              file=sys.stderr)
        return 1

    from apex_tpu.utils.compile_cache import configure_compile_cache

    say("cache", directory=configure_compile_cache())
    device_phase(devices)
    if args.multichip:
        multichip_phase(devices, **GPT2_345M, **MULTICHIP)
    else:
        train_phase(d, **GPT2_345M, **TRAIN)
        gc.collect()
        serve_phase(d, **GPT2_345M, **SERVE)
        gc.collect()
        parity_phase(num_heads=GPT2_345M["num_heads"],
                     head_dim=GPT2_345M["hidden"] // GPT2_345M["num_heads"],
                     batch=SERVE["batch_rungs"][-1], seq=TRAIN["seq"],
                     block_size=SERVE["block_size"],
                     pages=SERVE["page_rungs"][-1])
    print(result_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
