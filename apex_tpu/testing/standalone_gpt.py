"""Standalone GPT: the flagship transformer exercising TP x PP x DP x amp.

Parity with the reference's test model
(ref: apex/transformer/testing/standalone_gpt.py — embedding, parallel
transformer layers with fused softmax / checkpointing, tied LM head,
vocab-parallel loss, pipeline stage wiring via pre_process/post_process),
re-designed for one-program SPMD:

* ``GPTModel`` — full model for TP-only / single-chip runs.
* ``GPTEmbedding`` / ``GPTStage`` / ``GPTHead`` — the pipeline split:
  embedding and head live *outside* the pipelined region (the
  reference's pre/post_process flags, ref: schedules/common.py:18-107);
  each pipeline stage is a uniform block of layers.
* ``gpt_forward_pipelined`` — the assembled TP+PP forward: embed ->
  microbatch -> pipeline_forward over the pipe axis -> head ->
  vocab-parallel CE.  Called inside ``shard_map`` over the full
  (pipe, data, tensor) mesh; gradient sync across data/tensor emerges
  from boundary transposition (replicated params sum their cotangents).
"""
from __future__ import annotations

import contextlib
import functools
import os
import signal
import tempfile
import time
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import parallel_state
from ..transformer.enums import AttnMaskType
from ..transformer.layers import ParallelTransformer, ParallelTransformerLayer
from ..normalization import FusedLayerNorm
from ..transformer.tensor_parallel.cross_entropy import \
    vocab_parallel_cross_entropy
from ..transformer.tensor_parallel.layers import VocabParallelEmbedding

Dtype = Any


def unbox(tree):
    """Strip flax ``nn.Partitioned`` boxes, returning raw arrays."""
    return jax.tree.map(
        lambda l: l.unbox() if isinstance(l, nn.Partitioned) else l,
        tree, is_leaf=lambda l: isinstance(l, nn.Partitioned))


def boxed_specs(tree, extra_leading: int = 0,
                pipe_axis: str = parallel_state.PIPE_AXIS):
    """PartitionSpec tree from flax metadata, optionally prefixing leading
    (e.g. stacked-stage) axes with the pipe axis."""
    from jax.sharding import PartitionSpec as P

    def one(l):
        spec = (l.get_partition_spec()
                if isinstance(l, nn.Partitioned) else P())
        if extra_leading:
            spec = P(*((pipe_axis,) + tuple(spec)))
        return spec

    return jax.tree.map(one, tree,
                        is_leaf=lambda l: isinstance(l, nn.Partitioned))


class GPTEmbedding(nn.Module):
    """Token + learned position embeddings
    (ref: standalone_gpt.py Embedding)."""

    vocab_size: int
    hidden_size: int
    max_sequence_length: int
    embedding_dropout: float = 0.1
    dtype: Dtype = jnp.float32
    axis_name: Optional[str] = None

    def setup(self):
        self.word_embeddings = VocabParallelEmbedding(
            self.vocab_size, self.hidden_size, dtype=self.dtype,
            axis_name=self.axis_name, name="word_embeddings")
        self.position_embeddings = nn.Embed(
            self.max_sequence_length, self.hidden_size,
            embedding_init=nn.initializers.normal(stddev=0.02),
            dtype=self.dtype, name="position_embeddings")

    def __call__(self, tokens, deterministic: bool = True):
        s = tokens.shape[-1]
        h = self.word_embeddings(tokens)
        h = h + self.position_embeddings(jnp.arange(s, dtype=jnp.int32))
        if not deterministic and self.embedding_dropout > 0.0:
            key = self.make_rng("dropout")
            keep = jax.random.bernoulli(
                key, 1.0 - self.embedding_dropout, h.shape)
            h = jnp.where(keep, h / (1.0 - self.embedding_dropout),
                          jnp.zeros((), h.dtype))
        return h

    def attend(self, x):
        return self.word_embeddings.attend(x)


class GPTModel(nn.Module):
    """Full (non-pipelined) GPT: embedding -> transformer -> tied head.
    Returns vocab(-sharded in explicit mode) logits
    (ref: standalone_gpt.py GPTModel / post_language_model_processing)."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_attention_heads: int
    max_sequence_length: int
    ffn_hidden_size: Optional[int] = None
    attention_dropout: float = 0.1
    hidden_dropout: float = 0.1
    use_flash: bool = True
    checkpoint_activations: bool = False
    checkpoint_policy: str = "full"
    dtype: Dtype = jnp.float32
    axis_name: Optional[str] = None

    def setup(self):
        self.embedding = GPTEmbedding(
            self.vocab_size, self.hidden_size, self.max_sequence_length,
            embedding_dropout=self.hidden_dropout, dtype=self.dtype,
            axis_name=self.axis_name, name="embedding")
        self.transformer = ParallelTransformer(
            num_layers=self.num_layers, hidden_size=self.hidden_size,
            num_attention_heads=self.num_attention_heads,
            ffn_hidden_size=self.ffn_hidden_size,
            attn_mask_type=AttnMaskType.causal,
            attention_dropout=self.attention_dropout,
            hidden_dropout=self.hidden_dropout, use_flash=self.use_flash,
            checkpoint_activations=self.checkpoint_activations,
            checkpoint_policy=self.checkpoint_policy,
            dtype=self.dtype, axis_name=self.axis_name, name="transformer")

    def __call__(self, tokens, deterministic: bool = True):
        h = self.hidden_states(tokens, deterministic)
        # the scope a device trace reads the LM head + loss by
        # (``head_loss_ms.train``); make_step_fn puts the loss under it
        with jax.named_scope("apex.head_loss"):
            return self.embedding.attend(h)

    def hidden_states(self, tokens, deterministic: bool = True):
        """Final hidden states WITHOUT the tied-head projection — for
        memory-efficient losses that never materialize full logits
        (``contrib.xentropy.linear_cross_entropy_loss``)."""
        h = self.embedding(tokens, deterministic)
        return self.transformer(h, None, deterministic)


class GPTStage(nn.Module):
    """One pipeline stage: ``layers_per_stage`` uniform transformer layers
    (activation-shape preserving, as pipeline_forward requires)."""

    layers_per_stage: int
    hidden_size: int
    num_attention_heads: int
    ffn_hidden_size: Optional[int] = None
    attention_dropout: float = 0.1
    hidden_dropout: float = 0.1
    use_flash: bool = True
    dtype: Dtype = jnp.float32
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        for i in range(self.layers_per_stage):
            x = ParallelTransformerLayer(
                self.hidden_size, self.num_attention_heads,
                ffn_hidden_size=self.ffn_hidden_size,
                attn_mask_type=AttnMaskType.causal,
                attention_dropout=self.attention_dropout,
                hidden_dropout=self.hidden_dropout,
                use_flash=self.use_flash, dtype=self.dtype,
                axis_name=self.axis_name, name=f"layer_{i}")(
                    x, None, deterministic)
        return x


class GPTHead(nn.Module):
    """Final layernorm before the tied head
    (ref: standalone_gpt.py final_layernorm + logits)."""

    hidden_size: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        return FusedLayerNorm(self.hidden_size,
                              name="final_layernorm")(x).astype(self.dtype)


def gpt_loss(logits, labels, axis_name: Optional[str] = None,
             label_smoothing: float = 0.0):
    """Per-token mean LM loss over (possibly vocab-sharded) logits."""
    if axis_name is not None:
        losses = vocab_parallel_cross_entropy(
            logits, labels, label_smoothing=label_smoothing,
            axis_name=axis_name)
    else:
        # fused CE: single-consumer fp32 views in fwd AND bwd (the
        # logsumexp/take pair materializes an fp32 copy of the
        # (tokens, vocab) logits — see standalone_bert)
        from ..contrib.xentropy import softmax_cross_entropy_loss

        losses = softmax_cross_entropy_loss(
            logits, labels, label_smoothing, True)
    return jnp.mean(losses)


def gpt_forward_pipelined(embed_mod, stage_mod, head_mod,
                          embed_params, stage_params, head_params,
                          tokens, labels, *, num_microbatches: int,
                          tensor_axis: Optional[str],
                          pipe_axis: str = parallel_state.PIPE_AXIS,
                          data_axis: Optional[str] =
                          parallel_state.DATA_AXIS,
                          checkpoint_policy: Optional[str] = "full",
                          deterministic: bool = True):
    """TP+PP+DP GPT loss — call inside shard_map over the full mesh.

    ``tokens``/``labels`` arrive data-sharded [local_batch, seq];
    ``stage_params`` arrive pipe-sharded (leading stage dim of 1, as
    shard_map slices).  Returns the pmean (over data) scalar loss;
    differentiate *outside* the shard_map so boundary transposition
    performs the DP/TP gradient reductions.
    """
    from ..transformer.pipeline_parallel.schedules import pipeline_forward

    b, s = tokens.shape
    if b % num_microbatches != 0:
        raise ValueError(f"local batch {b} not divisible by "
                         f"num_microbatches {num_microbatches}")
    h = embed_mod.apply(embed_params, tokens, deterministic)
    mb = b // num_microbatches
    h_mb = h.reshape(num_microbatches, mb, s, h.shape[-1])

    def stage_fn(params, x):
        local = jax.tree.map(lambda p: p[0], params)
        return stage_mod.apply(local, x, deterministic)

    h_out = pipeline_forward(stage_fn, stage_params, h_mb,
                             axis_name=pipe_axis,
                             checkpoint_policy=checkpoint_policy)
    h_full = h_out.reshape(b, s, h.shape[-1])
    h_full = head_mod.apply(head_params, h_full)
    logits = embed_mod.apply(embed_params, h_full, method="attend")
    loss = gpt_loss(logits, labels, axis_name=tensor_axis)
    if data_axis is not None:
        loss = jax.lax.pmean(loss, data_axis)
    return loss


# ---------------------------------------------------------------------------
# Shared smoke-step construction — ONE build path for the train-smoke
# loop, the sanitizer smoke, and the compiled-graph auditor's entry
# registry (apex_tpu.testing.entry_points), so what CI lowers and
# audits is byte-for-byte what the drivers run.
# ---------------------------------------------------------------------------


class SmokeSetup(NamedTuple):
    """Everything a smoke train step needs, built once."""

    model: Any
    tokens: jnp.ndarray
    labels: jnp.ndarray
    params: Any
    amp_opt: Any
    amp_state: Any
    n_params: int


def make_smoke_setup(*, vocab: int = 64, hidden: int = 32,
                     num_heads: int = 4, num_layers: int = 2,
                     batch: int = 4, seq: int = 16,
                     opt_level: str = "O2", lr: float = 1e-3,
                     seed: int = 0, dtype=jnp.float32,
                     use_flash: bool = False,
                     pipeline: Optional[bool] = None) -> SmokeSetup:
    """Build the single-device GPT workload shared by
    :func:`train_smoke`, the sanitizer smoke, and the hlo-auditor entry
    registry — tiny by default.  ``dtype`` is the model COMPUTE dtype
    (the historical smoke default is fp32 even under O2 — params still
    cast per the policy); the O5 audit entry passes ``jnp.bfloat16`` so
    the lowered graph is a real low-precision policy region.
    ``use_flash`` routes attention through the Pallas flash kernels
    instead of materialised scores — what a real-width model needs to
    fit (``chip_smoke.py`` runs GPT-2 345M through here)."""
    from .. import amp
    from ..optimizers import fused_adam

    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=num_layers,
        num_attention_heads=num_heads, max_sequence_length=seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=use_flash,
        dtype=dtype)
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(jax.random.fold_in(key, 1),
                                (batch, seq), 0, vocab)
    labels = jnp.roll(tokens, -1, -1)
    variables = jax.jit(model.init)(key, tokens)
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(variables["params"]))
    params, amp_opt, amp_state = amp.initialize(
        variables["params"], fused_adam(lr), opt_level=opt_level,
        pipeline=pipeline)
    return SmokeSetup(model, tokens, labels, params, amp_opt,
                      amp_state, int(n_params))


def make_step_fn(setup: SmokeSetup):
    """The raw (unjitted) smoke train step: forward, scaled loss,
    backward, amp apply — ``step(params, amp_state) -> (params,
    amp_state, loss, gnorm, info)``.  The single build site the jitted
    wrappers (:func:`build_train_step`, :func:`build_train_step_scan`)
    all close over, so the per-step, deferred, and K-batched drivers
    cannot diverge in step semantics."""
    from ..transformer.pipeline_parallel.utils import param_l2_norm

    model, tokens, labels = setup.model, setup.tokens, setup.labels
    amp_opt = setup.amp_opt

    def _step(params, amp_state):
        def loss_fn(p):
            logits = model.apply({"params": p}, tokens)
            with jax.named_scope("apex.head_loss"):
                loss = gpt_loss(logits, labels)
            return amp_opt.scale_loss(loss, amp_state), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(params)
        new_params, new_state, info = amp_opt.apply_gradients(
            grads, amp_state, params)
        # the fused pipeline already measured the unscaled global norm
        # in its norm sweep; only the per-stage path re-sweeps the tree
        gnorm = info.grad_norm if info.grad_norm is not None else \
            param_l2_norm(grads) / amp_state.scaler.loss_scale
        return new_params, new_state, loss, gnorm, info

    return _step


def build_train_step(setup: SmokeSetup, *, telemetry=None):
    """The jitted smoke train step.  ``params`` and ``amp_state`` are
    DONATED — the loop rebinds both every step, and without donation
    XLA double-buffers the masters and optimizer state (the APX601
    finding this fixed: fp32 masters + m/v are the largest buffers in
    the step).  Returns ``step(params, amp_state) -> (params,
    amp_state, loss, gnorm, info)``.

    With ``telemetry`` (an :class:`apex_tpu.monitor.tracing.
    DeviceMetricsBuffer`) the step takes and returns the buffer's ring
    state as a third donated argument and appends this step's scalars
    (loss, grad-norm, loss-scale, overflow, skip count) **inside the
    jit** — the deferred-telemetry mode where the loop performs zero
    per-step host transfers: ``step(params, amp_state, tstate) ->
    (params, amp_state, tstate, loss, gnorm, info)``."""
    _step = make_step_fn(setup)
    if telemetry is None:
        return functools.partial(jax.jit, donate_argnums=(0, 1))(_step)
    return wrap_deferred_step(_step, telemetry)


def build_train_step_scan(setup: SmokeSetup, k: int, *, telemetry=None):
    """K train steps per jit call (the ISSUE-8 batched-step driver):
    the same smoke step as :func:`build_train_step`, iterated ``k``
    times inside one ``lax.scan`` — one dispatch, one compile, one
    donation round-trip per K steps, so the per-call host constant
    (dispatch + Python) is amortized K-fold.  See
    :func:`wrap_scan_step` for the carry/signature contract."""
    return wrap_scan_step(make_step_fn(setup), k, telemetry=telemetry)


def _append_step_metrics(telemetry, tstate, *, loss, gnorm, finite,
                         scale, skipped):
    """The ONE build site for the per-step metric set recorded into
    the device ring — shared by the deferred (per-step) wrapper and
    the scan body, so the drained series cannot diverge between K=0
    and K>=1 runs (add/rename a metric here and both modes get it)."""
    return telemetry.append(
        tstate, loss=loss, grad_norm=gnorm, loss_scale=scale,
        overflow=1.0 - finite.astype(jnp.float32),
        steps_skipped=skipped)


def wrap_deferred_step(step_fn, telemetry):
    """Wrap an unjitted ``step_fn(params, amp_state) -> (params,
    amp_state, loss, gnorm, info)`` smoke step with the in-jit
    deferred-telemetry append — ONE wrapper shared by the GPT and
    BERT drivers so the recorded metric set cannot diverge between
    them.  Returns the jitted three-argument deferred form (all
    arguments donated)."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step_deferred(params, amp_state, tstate):
        new_params, new_state, loss, gnorm, info = step_fn(params,
                                                           amp_state)
        tstate = _append_step_metrics(
            telemetry, tstate, loss=loss, gnorm=gnorm,
            finite=info.grads_finite, scale=info.loss_scale,
            skipped=info.steps_skipped)
        return new_params, new_state, tstate, loss, gnorm, info

    return step_deferred


def wrap_scan_step(step_fn, k: int, *, telemetry=None):
    """Wrap an unjitted ``step_fn(params, amp_state) -> (params,
    amp_state, loss, gnorm, info)`` smoke step into a jitted K-step
    ``lax.scan`` window — ONE wrapper shared by the GPT and BERT
    drivers (the scan sibling of :func:`wrap_deferred_step`).

    Everything the K steps mutate rides the scan carry: params (under
    the fused pipeline that includes the PackedMasters flat buffers
    reassembled into the model tree), the full amp state (masters +
    packed m/v + scaler), and — when ``telemetry`` (a
    :class:`~apex_tpu.monitor.tracing.DeviceMetricsBuffer` with
    ``capacity >= k``) is given — the telemetry ring, appended
    *inside* the scan body exactly as in the deferred step, so the
    whole window performs zero host transfers and every argument
    donates end-to-end (APX601: the scan entry is in the audited
    registry as ``gpt_train_step_scan``).

    The per-step amp semantics are unchanged — an overflow step inside
    the window skips its update and backs the scaler off exactly as it
    would standalone (tests prove K=1 vs K=4 bitwise-equal after N
    steps).  Returns, without telemetry, ``scan_step(params,
    amp_state) -> (params, amp_state, loss_last, gnorm_last,
    info_last)``; with telemetry the ring state joins as a third
    donated argument/result, matching the deferred signature."""
    if k < 1:
        raise ValueError(f"scan window must be >= 1 step, got {k}")
    meta = {}

    def _body(params, amp_state):
        new_params, new_state, loss, gnorm, info = step_fn(params,
                                                           amp_state)
        # static StepInfo structure, captured at trace time (the scan
        # body traces once): ys can only carry arrays
        meta["grads_checked"] = info.grads_checked
        meta["has_grad_norm"] = info.grad_norm is not None
        ys = (loss, gnorm, info.grads_finite, info.loss_scale,
              info.steps_skipped)
        return new_params, new_state, ys

    def _last(ys):
        from ..amp.mixed_precision import StepInfo

        loss, gnorm, finite, scale, skipped = ys
        info = StepInfo(
            grads_finite=finite[-1], loss_scale=scale[-1],
            steps_skipped=skipped[-1],
            grads_checked=meta["grads_checked"],
            grad_norm=gnorm[-1] if meta["has_grad_norm"] else None)
        return loss[-1], gnorm[-1], info

    if telemetry is None:
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def scan_step(params, amp_state):
            def body(carry, _):
                p, s = carry
                p, s, ys = _body(p, s)
                return (p, s), ys

            (params, amp_state), ys = jax.lax.scan(
                body, (params, amp_state), None, length=k)
            loss, gnorm, info = _last(ys)
            return params, amp_state, loss, gnorm, info

        return scan_step

    if telemetry.capacity < k:
        raise ValueError(
            f"telemetry ring capacity {telemetry.capacity} < scan "
            f"window {k}: a window's rows would overwrite each other "
            f"before the drain")

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def scan_step(params, amp_state, tstate):
        def body(carry, _):
            p, s, t = carry
            p, s, ys = _body(p, s)
            loss, gnorm, finite, scale, skipped = ys
            t = _append_step_metrics(
                telemetry, t, loss=loss, gnorm=gnorm, finite=finite,
                scale=scale, skipped=skipped)
            return (p, s, t), ys

        (params, amp_state, tstate), ys = jax.lax.scan(
            body, (params, amp_state, tstate), None, length=k)
        loss, gnorm, info = _last(ys)
        return params, amp_state, tstate, loss, gnorm, info

    return scan_step


def resolve_driver_mode(setup, scan_steps, drain_every, *, build_step,
                        build_step_scan):
    """Resolve a smoke driver's execution mode from ``(scan_steps,
    drain_every)`` — ONE copy of the scan/deferred policy shared by
    the GPT and BERT drivers: env-flag fallback
    (``APEX_TPU_SCAN_STEPS`` / ``APEX_TPU_TELEMETRY_DRAIN_EVERY``),
    the drain-cadence conflict check (the scan driver fixes the drain
    to the window size), DeferredTelemetry construction, and the
    ``(step, scan_factory)`` pair ``_run_smoke_loop`` consumes.
    ``build_step(setup, telemetry=)`` / ``build_step_scan(setup, n,
    telemetry=)`` are the driver's own builders.  Returns
    ``(scan_steps, telemetry, step, scan_factory)`` with exactly one
    of ``step`` / ``scan_factory`` non-None."""
    from ..analysis.flags import flag_int

    if scan_steps is None:
        scan_steps = flag_int("APEX_TPU_SCAN_STEPS")
    if drain_every is None:
        drain_every = flag_int("APEX_TPU_TELEMETRY_DRAIN_EVERY")
    if scan_steps and scan_steps > 0:
        from ..monitor.tracing import DeferredTelemetry

        if drain_every and drain_every > 0 \
                and drain_every != scan_steps:
            raise ValueError(
                f"scan_steps={scan_steps} fixes the telemetry drain "
                f"cadence to the window size; drain_every="
                f"{drain_every} conflicts (drop it, or match K)")
        telemetry = DeferredTelemetry(scan_steps)

        def scan_factory(n, _setup=setup, _buf=telemetry.buffer):
            return build_step_scan(_setup, n, telemetry=_buf)

        return scan_steps, telemetry, None, scan_factory
    telemetry = None
    if drain_every and drain_every > 0:
        from ..monitor.tracing import DeferredTelemetry

        telemetry = DeferredTelemetry(drain_every)
    step = build_step(
        setup, telemetry=telemetry.buffer if telemetry else None)
    return scan_steps, telemetry, step, None


# ---------------------------------------------------------------------------
# Monitored smoke train loop — the run-telemetry acceptance path
# ---------------------------------------------------------------------------

def make_smoke_monitor(jsonl, sink, *, tokens_per_step, flops_per_step,
                       stall_timeout, run_attrs, escalation=None,
                       watchdog_trace_dir=None):
    """Monitor bootstrap shared by the GPT/BERT smoke drivers: default
    sink selection (JSONL file if a path was given, else in-memory),
    watchdog wiring (optionally escalated through an
    ``apex_tpu.resilience.EscalationPolicy``; ``watchdog_trace_dir``
    arms the stall-alarm ``jax.profiler`` capture of a wedged step),
    and close-ownership — the monitor closes the sink only when it
    created it, so a caller-provided sink stays usable after the
    run."""
    from ..monitor import JsonlSink, MemorySink, StepMonitor, Watchdog

    own_sink = sink is None
    if sink is None:
        sink = JsonlSink(jsonl) if jsonl else MemorySink()
    return StepMonitor(
        sink, tokens_per_step=tokens_per_step,
        flops_per_step=flops_per_step,
        watchdog=Watchdog(sink, stall_timeout=stall_timeout,
                          trace_dir=watchdog_trace_dir,
                          on_alarm=None if escalation is None
                          else escalation.notify),
        run_attrs=run_attrs, close_sink=own_sink)


def _boundary_tail(done, prev_done, step_label, *, monitor, ckpt,
                   ckpt_every, save, part, wf, capture, escalation,
                   autoresume, wf_extras=None):
    """The per-boundary resilience/observability tail shared by
    :func:`run_monitored_steps` (boundary = every step) and
    :func:`run_scan_windows` (boundary = every K-step window edge):
    escalation poll -> checkpoint cadence -> waterfall close ->
    capture poll -> termination poll.  ``done`` is the steps-done
    count the checkpoint is cut at, ``prev_done`` the count at the
    previous boundary; ``step_label`` the step number events are
    stamped with (the window's last step under scan).  The checkpoint
    cadence is a *crossing* check — save when ``(prev_done, done]``
    contains a multiple of ``ckpt_every`` — so a cadence that is not a
    multiple of the scan window K still checkpoints at the first edge
    past each cadence point instead of aliasing to lcm(K, ckpt_every)
    (or never).  At K=1 this is exactly ``done % ckpt_every == 0``.
    Returns True when a termination request ended the run (the caller
    breaks), False to continue."""
    esc = escalation.pending() if escalation is not None else None
    if esc is not None:
        from ..resilience import (CHECKPOINT_THEN_ABORT,
                                  EscalationAbort)

        if esc.action == CHECKPOINT_THEN_ABORT and ckpt is not None:
            save(done, sync=True)
        monitor.event("resilience", "escalation_abort", step=step_label,
                      alarm=esc.alarm, action=esc.action,
                      checkpointed=esc.action == CHECKPOINT_THEN_ABORT
                      and ckpt is not None)
        raise EscalationAbort(esc.alarm, esc.action, step=step_label)
    saved = False
    with part("ckpt_io"):
        # always closes (zero-length when no manager/cadence hit) so
        # the canonical waterfall shape is uniform per boundary
        ce = max(1, ckpt_every)
        if ckpt is not None and done // ce > prev_done // ce:
            save(done)
            saved = True
    if wf is not None:
        wf.end_step(monitor, step=step_label, **(wf_extras or {}))
    if capture is not None:
        capture.poll(step_label)
    if autoresume is not None and autoresume.termination_requested():
        if ckpt is not None:
            if not saved:
                save(done)
            ckpt.wait()  # final checkpoint must be durable
        if autoresume.marker_dir is not None:
            autoresume.mark_clean_exit(done)
        monitor.event("resilience", "preempt_exit", step=step_label,
                      value=done, source=autoresume.source)
        return True
    return False


def run_monitored_steps(step_fn, params, amp_state, steps, monitor,
                        timers, lr=None, *, start_step: int = 0,
                        ckpt=None, ckpt_every: int = 1, amp_opt=None,
                        autoresume=None, escalation=None, fault=None,
                        sanitizer=None, trace=None, telemetry=None):
    """Drive ``step_fn(params, amp_state) -> (params, amp_state, loss,
    grad_norm, step_info)`` for steps ``[start_step, steps)``,
    recording each through an :class:`apex_tpu.monitor.StepMonitor` and
    exporting the per-step phase ``timers`` into the same event log.
    Shared by the GPT and BERT smoke drivers.

    The observability wiring (both optional):

    * ``trace`` — an :class:`apex_tpu.monitor.tracing.TraceSession`:
      every step is attributed over the canonical waterfall parts
      (``data_load`` / ``dispatch`` / ``device_compute`` from the
      block_until_ready boundary / ``telemetry_drain`` / ``ckpt_io`` /
      ``other`` residual), emitted per step as an ``attr`` event plus
      host spans, and the capture trigger is polled at each boundary.
    * ``telemetry`` — an :class:`apex_tpu.monitor.tracing.
      DeferredTelemetry`; ``step_fn`` must then be the deferred variant
      from ``build_train_step(setup, telemetry=buf)``.  Per-step
      scalars stay device-resident and drain every K steps through one
      explicit ``jax.device_get`` — the loop performs **zero** per-step
      host transfers (provable with ``sanitize(transfer_guard=
      "disallow", transfer_scope="device_to_host")``).  Deferred mode
      skips ``fault.observed_loss`` (losses are not host-visible at
      step time).

    The resilience wiring is all optional (None = PR-2 behavior):

    * ``ckpt`` — an ``apex_tpu.utils.CheckpointManager``; after step
      ``i`` completes, step ``i+1`` ("steps done") is saved every
      ``ckpt_every`` steps (async — the loop keeps running).
    * ``autoresume`` — polled at each step boundary; on a termination
      request the loop cuts a final *synchronous* checkpoint, writes
      the clean-exit marker, emits ``preempt_exit``, and returns early.
    * ``escalation`` — polled at each step boundary; a latched alarm
      raises :class:`~apex_tpu.resilience.EscalationAbort` (after a
      synchronous checkpoint iff the action says so) for
      ``run_resumable`` to catch and restart.
    * ``fault`` — an ``apex_tpu.resilience.FaultInjector`` driving
      deterministic failures (``before_step`` / ``observed_loss``).
    * ``sanitizer`` — an :class:`apex_tpu.analysis.Sanitizer`; its
      ``step()`` runs at each step boundary, so a post-warmup
      recompile fails the run (docs/api/analysis.md).

    Returns ``(params, amp_state, last_loss, steps_done)``.
    """
    import contextlib as _ctx

    loss_f = None
    done = start_step
    wf = trace.waterfall if trace is not None else None
    capture = trace.capture if trace is not None else None

    def part(name):
        return wf.part(name) if wf is not None else _ctx.nullcontext()

    def save(step, sync=False):
        ckpt.save(step, params, amp_opt, amp_state)
        if sync:
            ckpt.wait()

    for i in range(start_step, steps):
        if wf is not None:
            wf.begin_step(i)
        with part("data_load"):
            # the smoke workload is synthetic (tokens fixed at build);
            # a real driver wraps its loader fetch here.  The canonical
            # span still closes every step so the waterfall shape is
            # uniform across drivers.
            if fault is not None:
                fault.before_step(i)
        monitor.start_step(i)
        timers("step").start()
        with part("dispatch"):
            # async dispatch: this returns at enqueue; the device runs on
            if telemetry is not None:
                params, amp_state, loss, gnorm, info = telemetry.step(
                    step_fn, params, amp_state, step=i)
            else:
                params, amp_state, loss, gnorm, info = step_fn(
                    params, amp_state)
        with part("device_compute"):
            # the block_until_ready boundary: host time spent waiting
            # on the device (timers("step") syncs on the step outputs)
            timers("step").stop(wait_on=loss)
        with part("telemetry_drain"):
            if telemetry is None:
                loss_f = float(loss)
                if fault is not None:
                    loss_f = fault.observed_loss(i, loss_f)
                monitor.end_step(i, loss=loss_f, grad_norm=gnorm,
                                 lr=lr, scaler=info)
            else:
                # host-clock metrics only (step_ms, tokens/s, MFU) —
                # no device value is touched at step time
                monitor.end_step(i, lr=lr)
                if telemetry.maybe_drain(monitor):
                    loss_f = telemetry.last_metrics.get("loss")
            timers.events(monitor, i, reset=True)
            if trace is not None:
                trace.flush(monitor, step=i)
        if sanitizer is not None:
            sanitizer.step()  # post-warmup recompile -> raise here
        done = i + 1
        if _boundary_tail(done, i, i, monitor=monitor, ckpt=ckpt,
                          ckpt_every=ckpt_every, save=save, part=part,
                          wf=wf, capture=capture, escalation=escalation,
                          autoresume=autoresume):
            break
    if telemetry is not None and telemetry.maybe_drain(monitor,
                                                       force=True):
        loss_f = telemetry.last_metrics.get("loss")
    return params, amp_state, loss_f, done


def run_scan_windows(scan_factory, k, params, amp_state, steps, monitor,
                     timers, telemetry, *, lr=None, start_step: int = 0,
                     ckpt=None, ckpt_every: int = 1, amp_opt=None,
                     autoresume=None, escalation=None, fault=None,
                     sanitizer=None, trace=None):
    """The K-batched twin of :func:`run_monitored_steps`: drive
    ``ceil((steps - start_step) / k)`` scan windows, each one jit call
    running ``k`` train steps (``scan_factory(k)`` builds the window
    function — :func:`build_train_step_scan`; a trailing remainder
    window builds its own shorter scan, one extra compile the sanitize
    contract documents).  Every host-side boundary lands on K-step
    edges:

    * **dispatch-free hot path** — each window is AOT-compiled
      (``jit(...).lower().compile()``, timed and emitted as one
      ``compile``/``aot_compile`` event) and the loop calls the
      compiled executable, so the steady-state loop can never retrace;
      with the persistent cache configured
      (``APEX_TPU_COMPILE_CACHE_DIR``) a warmed host loads it from
      disk.
    * **telemetry** — per-step scalars accumulate in the device ring
      *inside* the scan body; :meth:`DeferredTelemetry.maybe_drain`
      performs one explicit ``device_get`` per window (ceil(N/K)
      drains for the run), re-emitting the full per-step metric
      series with reconstructed step numbers.
    * **waterfall** — one attribution row per window, stamped
      ``scan_k``: ``dispatch`` is the single enqueue for K steps,
      ``device_compute`` the block on its outputs — the amortization
      shows up directly as ``wall_device_ratio`` rising with K.
    * **resilience** — fault injection, the escalation poll,
      checkpoint cadence (a crossing check: the first window edge at
      or past each ``ckpt_every`` multiple saves, so a cadence that
      is not a multiple of K never aliases to silence) and
      ``autoresume.termination_requested()`` all run between windows;
      a kill mid-window resumes from the last K-boundary checkpoint.
    * **sanitizer** — ``sanitizer.step()`` per window: for N a
      multiple of K, exactly one compile (the first window's, during
      warmup) for the whole run.

    Returns ``(params, amp_state, last_loss, steps_done)`` with
    ``steps_done`` always on a window edge.
    """
    import contextlib as _ctx
    import time as _time

    if k < 1:
        raise ValueError(f"scan_steps must be >= 1, got {k}")
    loss_f = None
    done = start_step
    wf = trace.waterfall if trace is not None else None
    capture = trace.capture if trace is not None else None

    def part(name):
        return wf.part(name) if wf is not None else _ctx.nullcontext()

    def save(step, sync=False):
        ckpt.save(step, params, amp_opt, amp_state)
        if sync:
            ckpt.wait()

    compiled = {}

    def window_fn(n, *args):
        ex = compiled.get(n)
        if ex is None:
            t0 = _time.perf_counter()
            ex = scan_factory(n).lower(*args).compile()
            compiled[n] = ex
            monitor.event("compile", "aot_compile",
                          value=round((_time.perf_counter() - t0) * 1e3,
                                      2), scan_k=n)
        return ex

    # AOT-precompile every window length this run will use BEFORE the
    # first step: compile cost lands in its own `compile` events (and,
    # under --sanitize, in the warmup bucket), never in a window's
    # waterfall — the steady-state `dispatch` part measures dispatch,
    # not a hidden cold start.  Lengths: the full K window plus (for
    # runs where steps - start_step is not a multiple of K) the
    # trailing remainder.
    remaining = steps - start_step
    if remaining > 0:
        lengths = {min(k, remaining)}
        if remaining > k and remaining % k:
            lengths.add(remaining % k)
        for n in sorted(lengths, reverse=True):
            window_fn(n, params, amp_state, telemetry.state)

    per_step_tokens = monitor.tokens_per_step
    per_step_flops = monitor.flops_per_step
    w_start = start_step
    try:
        while w_start < steps:
            k_eff = min(k, steps - w_start)
            w_last = w_start + k_eff - 1
            if wf is not None:
                wf.begin_step(w_start)
            with part("data_load"):
                # synthetic smoke workload (see run_monitored_steps);
                # a fault aimed anywhere in this window fires at its
                # start edge (the only host boundary that exists)
                if fault is not None:
                    fault.before_window(w_start, k_eff)
            monitor.start_step(w_start)
            timers("step").start()
            with part("dispatch"):
                # ONE enqueue for k_eff steps — the amortization
                fn = window_fn(k_eff, params, amp_state,
                               telemetry.state)
                params, amp_state, loss, gnorm, info = \
                    telemetry.scan_window(fn, params, amp_state,
                                          start=w_start, k=k_eff)
            with part("device_compute"):
                timers("step").stop(wait_on=loss)
            with part("telemetry_drain"):
                # host-clock metrics for the whole window (step_ms is
                # the window wall; tokens/MFU scale by k_eff)
                if per_step_flops:
                    monitor.flops_per_step = per_step_flops * k_eff
                monitor.end_step(w_last, lr=lr,
                                 tokens=(per_step_tokens or 0) * k_eff
                                 or None)
                if telemetry.maybe_drain(monitor):
                    loss_f = telemetry.last_metrics.get("loss")
                timers.events(monitor, w_last, reset=True)
                if trace is not None:
                    trace.flush(monitor, step=w_last)
            if sanitizer is not None:
                sanitizer.step()
            done = w_start + k_eff
            if _boundary_tail(done, w_start, w_last, monitor=monitor,
                              ckpt=ckpt, ckpt_every=ckpt_every,
                              save=save, part=part, wf=wf,
                              capture=capture, escalation=escalation,
                              autoresume=autoresume,
                              wf_extras={"scan_k": k_eff}):
                break
            w_start = done
    finally:
        monitor.flops_per_step = per_step_flops
    if telemetry.maybe_drain(monitor, force=True):
        loss_f = telemetry.last_metrics.get("loss")
    return params, amp_state, loss_f, done


def train_smoke(steps: int = 8, *, jsonl: Optional[str] = None,
                sink=None, vocab: int = 64, hidden: int = 32,
                num_heads: int = 4, num_layers: int = 2, batch: int = 4,
                seq: int = 16, opt_level: str = "O2", lr: float = 1e-3,
                stall_timeout: float = 300.0, seed: int = 0,
                ckpt_dir: Optional[str] = None, ckpt_every: int = 1,
                ckpt_keep: int = 3, resume: bool = True,
                fault=None, autoresume="auto", escalation=None,
                return_state: bool = False, sanitize: bool = False,
                trace_dir: Optional[str] = None,
                drain_every: Optional[int] = None,
                scan_steps: Optional[int] = None,
                use_flash: bool = False, dtype=jnp.float32):
    """Tiny single-device GPT train loop wired end-to-end through
    :mod:`apex_tpu.monitor` — the CPU telemetry smoke (exercised by
    tools/ci.sh on every run): step metrics (loss, grad-norm, lr,
    tokens/s, step ms, MFU), amp loss-scale/overflow events (the O2
    dynamic scaler genuinely backs off in fp16 at init scale 2^16),
    phase-timer events, and a live stall watchdog — all into one JSONL
    that ``tools/monitor_summary.py`` renders.

    Pass ``jsonl`` for a file log, or ``sink`` (e.g. a ``MemorySink``)
    to capture events in-process; with neither, events go to a
    throwaway ``MemorySink``.  Returns the final loss (host float), or
    ``(loss, params, amp_state, steps_done)`` with ``return_state=True``
    (how the kill-and-resume tests compare runs bitwise).  The monitor
    is closed on exit; it closes the sink too unless the caller
    provided one.

    With ``ckpt_dir`` the loop is **preemption-safe** (the tier-1
    resilience acceptance path, see docs/api/resilience.md): every
    ``ckpt_every`` steps an async checkpoint is cut; at start the run
    auto-resumes from the latest *valid* step (corrupt ones skipped +
    GC'd); ``autoresume="auto"`` installs a SIGTERM/SIGINT
    :class:`~apex_tpu.resilience.AutoResume` whose termination request
    produces a final synchronous checkpoint plus the ``CLEAN_EXIT.json``
    marker (pass an instance to share one, or None to disable).
    ``fault`` is a fault spec string or
    :class:`~apex_tpu.resilience.FaultInjector` (``"sigterm@4"``,
    ``"nan@3,crash@5"``, ...); ``escalation`` an
    :class:`~apex_tpu.resilience.EscalationPolicy` latched into the
    watchdog.  A crashing step emits a terminal ``run_error`` event
    before the exception propagates.

    ``trace_dir`` enables the wall-time attribution tracer
    (:mod:`apex_tpu.monitor.tracing`): per-step waterfall rows + host
    spans in the event log, a ``trace.chrome.json`` Perfetto artifact
    in the directory, and the on-demand capture trigger per the
    ``APEX_TPU_TRACE_*`` flags.  ``drain_every`` >= 1 switches to
    sync-free deferred telemetry (device metrics ring drained every K
    steps — zero per-step host transfers; with ``sanitize=True`` the
    transfer guard proves it); None reads
    ``APEX_TPU_TELEMETRY_DRAIN_EVERY``, 0 is the classic synchronous
    path.

    ``scan_steps`` >= 1 switches to the **batched-step scan driver**
    (:func:`build_train_step_scan` + :func:`run_scan_windows`): K
    train steps per jit call with amp state and the telemetry ring in
    the scan carry, AOT-compiled windows, ceil(N/K) telemetry drains,
    and checkpoint/watchdog/waterfall boundaries on K-step edges; None
    reads ``APEX_TPU_SCAN_STEPS``, 0 is the classic per-step loop.
    Scan mode implies deferred telemetry at cadence K (a conflicting
    explicit ``drain_every`` is rejected — the window IS the drain
    cadence).

    ``use_flash`` and ``dtype`` (the model compute dtype) pass through
    to :func:`make_smoke_setup`: with ``use_flash=True,
    dtype=jnp.bfloat16, opt_level="O5"`` and real widths this same
    loop trains GPT-2 345M on one chip.
    """
    from ..transformer.pipeline_parallel.utils import Timers
    from ..utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    setup = make_smoke_setup(
        vocab=vocab, hidden=hidden, num_heads=num_heads,
        num_layers=num_layers, batch=batch, seq=seq,
        opt_level=opt_level, lr=lr, seed=seed, dtype=dtype,
        use_flash=use_flash)
    scan_steps, telemetry, step, scan_factory = resolve_driver_mode(
        setup, scan_steps, drain_every,
        build_step=build_train_step,
        build_step_scan=build_train_step_scan)
    params, amp_opt, amp_state = (setup.params, setup.amp_opt,
                                  setup.amp_state)
    n_params = setup.n_params
    flops = 6.0 * n_params * batch * seq \
        + 12.0 * num_layers * hidden * batch * seq * seq
    monitor = make_smoke_monitor(
        jsonl, sink, tokens_per_step=batch * seq, flops_per_step=flops,
        stall_timeout=stall_timeout, escalation=escalation,
        run_attrs={"driver": "standalone_gpt.train_smoke",
                   "params": int(n_params), "opt_level": opt_level,
                   "batch": batch, "seq": seq,
                   "scan_steps": scan_steps or 0,
                   "telemetry": "deferred" if telemetry else "sync"})
    timers = Timers()
    trace = None
    if trace_dir is not None:
        from ..monitor.tracing import TraceSession

        trace = TraceSession.from_flags(trace_dir, sink=monitor,
                                        timers=timers)
    return _run_smoke_loop(
        step, params, amp_opt, amp_state, steps, monitor, timers, lr=lr,
        ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, ckpt_keep=ckpt_keep,
        resume=resume, fault=fault, autoresume=autoresume,
        escalation=escalation, return_state=return_state,
        sanitize=sanitize, trace=trace, telemetry=telemetry,
        scan_steps=scan_steps or 0, scan_factory=scan_factory)


def _run_smoke_loop(step_fn, params, amp_opt, amp_state, steps, monitor,
                    timers, *, lr, ckpt_dir, ckpt_every, ckpt_keep,
                    resume, fault, autoresume, escalation, return_state,
                    sanitize: bool = False, trace=None, telemetry=None,
                    scan_steps: int = 0, scan_factory=None):
    """Resilience-wired driver shell shared by the GPT and BERT smokes:
    checkpoint manager + auto-resume bootstrap around
    :func:`run_monitored_steps` (or, with ``scan_steps`` >= 1,
    :func:`run_scan_windows` — K steps per jit call via
    ``scan_factory``), ``run_error`` emission on a crashing step, and
    guaranteed teardown (watchdog heartbeat, JSONL sink, pending async
    saves, trace session -> Chrome artifact) via ``try/finally``.
    With ``telemetry`` (deferred mode — always on under the scan
    driver) the ``sanitize`` contract tightens: the device→host
    transfer guard is armed too, so ANY per-step implicit host
    readback fails the run — the zero-transfer proof, not just the
    recompile budget.  Under the scan driver the recompile budget
    additionally proves ONE compile per run when ``steps`` is a
    multiple of K (a trailing remainder window compiles its own
    shorter scan, but :func:`run_scan_windows` AOT-precompiles every
    window length before the first step, so both compiles land in the
    warmup bucket and the budget stays clean for any N)."""
    from ..monitor.events import ThreadExceptionCapture
    from ..resilience import AutoResume, parse_fault
    from ..utils import CheckpointManager

    if isinstance(fault, str):
        fault = parse_fault(fault)
    mgr = None
    own_autoresume = False
    loss_f = None
    done = 0
    # threading.excepthook capture: a watchdog-heartbeat (or any
    # other background) thread dying mid-run becomes a run_error
    # event at crash time and a raised failure after teardown,
    # instead of a stderr traceback and a silently dead thread
    thread_cap = ThreadExceptionCapture(monitor).install()
    try:
        if escalation is not None:
            escalation.reset()  # a fresh attempt re-arms the policy —
            # a stale latch from the previous attempt would otherwise
            # abort every retry at its first step boundary
        start_step = 0
        if ckpt_dir is not None:
            mgr = CheckpointManager(ckpt_dir, keep=ckpt_keep,
                                    sink=monitor)
            if autoresume == "auto":
                autoresume = AutoResume(marker_dir=mgr.directory,
                                        sink=monitor).install()
                own_autoresume = True
            if resume and mgr.latest_valid_step() is not None:
                params, amp_state, _, start_step = mgr.restore(
                    params, amp_opt, amp_state)
                monitor.event("resilience", "run_resumed",
                              value=start_step, directory=mgr.directory)
        if autoresume == "auto":  # no ckpt_dir to anchor a marker
            autoresume = None
        if autoresume is not None and autoresume.marker_dir:
            autoresume.clear_clean_exit()  # marker = THIS run's exit
        done = start_step
        with contextlib.ExitStack() as stack:
            san = None
            if sanitize:
                # smoke contract: the jitted step compiles once during
                # the first (warmup) step and never again — a
                # post-warmup recompile raises RecompileBudgetExceeded
                # out of the loop.  Deferred telemetry additionally
                # arms the d->h transfer guard: the ring's explicit
                # device_get drain is the ONLY permitted readback
                # (sync mode keeps transfers unguarded — its per-step
                # float(loss) is an expected, explicit design choice).
                from ..analysis import sanitize as sanitize_ctx

                san = stack.enter_context(sanitize_ctx(
                    transfer_guard=("disallow" if telemetry is not None
                                    else None),
                    transfer_scope="device_to_host",
                    recompile_budget=0, warmup_steps=1))
            if scan_steps and scan_steps > 0:
                params, amp_state, loss_f, done = run_scan_windows(
                    scan_factory, scan_steps, params, amp_state, steps,
                    monitor, timers, telemetry, lr=lr,
                    start_step=start_step, ckpt=mgr,
                    ckpt_every=ckpt_every, amp_opt=amp_opt,
                    autoresume=autoresume, escalation=escalation,
                    fault=fault, sanitizer=san, trace=trace)
            else:
                params, amp_state, loss_f, done = run_monitored_steps(
                    step_fn, params, amp_state, steps, monitor, timers,
                    lr=lr, start_step=start_step, ckpt=mgr,
                    ckpt_every=ckpt_every, amp_opt=amp_opt,
                    autoresume=autoresume, escalation=escalation,
                    fault=fault, sanitizer=san, trace=trace,
                    telemetry=telemetry)
    except BaseException as e:
        # terminal record first — the re-raise may end the process
        monitor.event("run", "run_error", step=done,
                      error=type(e).__name__, message=str(e)[:200])
        raise
    finally:
        if telemetry is not None:
            # a crash between drains must not lose the ring's pending
            # steps — they are exactly the losses needed to diagnose
            # it.  The guard context is closed by now, so the explicit
            # fetch is unconditionally legal.
            try:
                telemetry.maybe_drain(monitor, force=True)
            except Exception as e:
                from ..utils.log_util import get_logger

                get_logger(__name__).warning(
                    "final telemetry drain failed: %s", str(e)[:160])
        # Nested so one teardown failure cannot skip the next: the sink
        # close must not strand a pending async save, and a stranded
        # signal handler would swallow the process's next SIGTERM.
        try:
            if trace is not None:
                # flush remaining spans into the (still-open) sink and
                # commit the Chrome artifact before the sink closes
                trace.close(monitor)
        finally:
            try:
                monitor.close()
            finally:
                try:
                    if mgr is not None:
                        mgr.close()  # pending async saves become durable
                finally:
                    try:
                        if own_autoresume:
                            autoresume.uninstall()
                    finally:
                        thread_cap.uninstall()
    thread_cap.raise_first()
    if return_state:
        return loss_f, params, amp_state, done
    return loss_f


# ---------------------------------------------------------------------------
# Serving smoke — the continuous-batching acceptance path (ISSUE-9)
# ---------------------------------------------------------------------------

def serve_smoke(num_requests: int = 6, *, jsonl: Optional[str] = None,
                sink=None, vocab: int = 64, hidden: int = 32,
                num_heads: int = 4, num_layers: int = 2,
                max_seq: int = 64, max_new_tokens: int = 6,
                seed: int = 0, dtype=jnp.float32,
                policy: Optional[str] = None,
                decode_attention: str = "kernel",
                prefill_flash: bool = True,
                num_blocks: Optional[int] = None,
                block_size: Optional[int] = None,
                kv_dtype: Optional[str] = None, ladder=None,
                sanitize: bool = False, fault=None,
                autoresume="auto", stall_timeout: float = 300.0,
                trace_dir: Optional[str] = None,
                tick_every: Optional[int] = None,
                snapshot="auto",
                speculate_k: Optional[int] = None,
                prefill_chunk: Optional[int] = None,
                prefix_share: Optional[bool] = None,
                draft: str = "self",
                deadline_ms: Optional[float] = None,
                shed=None,
                journal_path: Optional[str] = None,
                supervise: bool = False,
                max_restarts: int = 3,
                escalation="auto",
                backoff_base: float = 0.05,
                metrics_port: Optional[int] = None,
                metrics_linger: float = 0.0,
                ep: Optional[int] = None,
                moe_experts: Optional[int] = None,
                return_engine: bool = False):
    """Continuous-batched serving smoke: a tiny GPT serves
    ``num_requests`` mixed-length prompts through the
    :mod:`apex_tpu.serving` engine — prefill via the flash forward
    kernel, decode via the paged flash-decode kernel, admissions and
    evictions interleaving with jitted decode steps — and reports
    decode tokens/s plus p50/p99 per-token latency through the
    monitor stack (the ``--serve`` acceptance path, tools/ci.sh step
    10).

    ``sanitize=True`` proves the bucket-ladder compile discipline:
    every (batch, pages) bucket is AOT-compiled by ``engine.warmup()``
    before traffic, so the whole serve holds a post-warmup recompile
    budget of ZERO — a shape leaking past the ladder fails the run.
    ``fault`` accepts the resilience spec syntax (``"sigterm@3"``
    fires at decode tick 3) and ``autoresume="auto"`` installs the
    flag-only SIGTERM handler: a mid-serve termination stops
    admissions, frees every block, marks in-flight requests
    preempted, and still returns a full summary — the clean-drain
    contract.  ``decode_attention="reference"`` swaps the kernel for
    the dense gather twin (the naive decode baseline the kernel is
    held against).

    The ISSUE-12 decode fast path rides the same smoke:
    ``speculate_k=K`` builds a draft GPT (``draft="self"`` reuses the
    target's weights — the acceptance-rate ceiling and the CI
    machinery proof; ``draft="narrow"`` initializes a 1-layer,
    half-width model — the low-acceptance rollback stress) and the
    engine emits 1..K+1 tokens per tick, token-for-token identical to
    plain greedy decode; ``prefix_share=True`` turns on copy-on-write
    prompt-prefix sharing; ``prefill_chunk=N`` splits admissions into
    N-token chunks interleaved with decode.  All three default to
    their ``APEX_TPU_SERVE_*`` flags.

    Per-request telemetry (ISSUE-11) is always on: every request's
    lifecycle chain (``request_submitted → request_admitted →
    request_first_token → request_done``) and the per-tick
    ``serve_tick`` engine gauges (cadence ``tick_every`` /
    ``APEX_TPU_SERVE_TICK_EVERY``) land in the event log, and the
    summary carries queue-wait/TTFT/ITL percentiles.  ``trace_dir``
    additionally writes ``<dir>/serve.chrome.json`` — one Perfetto
    lane per request with queued/prefill/decode phases — and arms the
    watchdog's stall-capture under ``<dir>/stall``.  ``snapshot=
    "auto"`` installs the on-demand engine snapshot trigger
    (SIGUSR1 + ``APEX_TPU_SERVE_SNAPSHOT_FILE``); pass an explicit
    :class:`~apex_tpu.serving.SnapshotTrigger` or None.

    Serving resilience (ISSUE-13) rides the same smoke:
    ``deadline_ms`` stamps a default request deadline (flag:
    ``APEX_TPU_SERVE_DEADLINE_MS``), ``shed`` a
    :class:`~apex_tpu.serving.ShedPolicy` (flags:
    ``APEX_TPU_SERVE_SHED_*``), ``journal_path`` a crash-safe
    :class:`~apex_tpu.serving.RequestJournal` (default:
    ``APEX_TPU_SERVE_JOURNAL_DIR``/serve.journal.jsonl when that flag
    is set), and ``supervise=True`` runs the engine under
    :func:`~apex_tpu.serving.run_serving` — bounded-backoff restarts
    with journal replay, so ``--fault crash@K`` recovers instead of
    dying (requires a journal).  ``escalation="auto"`` installs the
    serve watchdog policy (stall → snapshot-then-drain); pass an
    :class:`~apex_tpu.resilience.EscalationPolicy` or None.

    ``policy`` selects an amp serving tier (ISSUE-16): ``"O5"`` casts
    the model to bf16; ``"Q8"`` additionally quantizes every matmul
    weight to per-channel int8 (:func:`apex_tpu.ops.quant_matmul.
    quantize_weights`), so the serve exercises the quantized decode
    path end to end — the ``--policy Q8`` CI smoke.

    ``ep=N`` (flag: ``APEX_TPU_SERVE_EP``) serves expert-parallel
    (ISSUE-19): the model's MLPs expand to a ``moe_experts``-way
    Switch MoE (:func:`~apex_tpu.serving.expand_moe_weights`;
    default ``2*ep`` experts) and the engine runs under an
    :class:`~apex_tpu.serving.EPContext` — expert stacks sharded over
    N devices, attention and cache replicated, the fused routing +
    capacity-chunked overlapped all_to_all exchange per MoE layer.
    The same ladder/warmup/sanitize discipline applies: the EP serve
    holds a post-warmup recompile budget of ZERO.  Does not compose
    with ``--policy Q8`` or speculative decoding.

    The live metrics plane (ISSUE-17) arms with ``metrics_port``
    (flag: ``APEX_TPU_METRICS_PORT``; an explicit ``0`` picks an
    ephemeral port): a :class:`~apex_tpu.monitor.MetricsServer`
    daemon thread serves ``/metrics`` (Prometheus text exposition),
    ``/healthz`` (503 while draining; SLO-burn / shed / escalation
    aware) and ``/varz`` (the SIGUSR1 snapshot payload) from
    lock-free per-tick publishes — scrapes never touch the engine.
    SLO objectives come from the ``APEX_TPU_SLO_*`` flags
    (``ServingEngine(slo="auto")``).  ``metrics_linger`` keeps the
    server up that many seconds after the drain so an external probe
    (tools/metrics_probe.py, ci.sh step 15) can observe the
    ``/healthz`` flip before teardown.

    Returns the :class:`~apex_tpu.serving.ServeSummary` (with
    ``return_engine=True``, ``(summary, engine)`` — how tests read
    per-request token streams)."""
    import numpy as np

    from ..resilience import AutoResume, parse_fault, serve_policy
    from ..serving import (BucketLadder, Request, RequestJournal,
                           ServingEngine, ServingModelConfig,
                           SnapshotTrigger, default_cache_config,
                           extract_serving_weights, run_serving)

    pol = None
    if policy is not None:
        from ..amp import get_policy
        pol = get_policy(policy)
        if pol.cast_model_type is not None:
            dtype = pol.cast_model_type
    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=num_layers,
        num_attention_heads=num_heads, max_sequence_length=max_seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=dtype)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(model.init)(
        key, jnp.zeros((1, min(8, max_seq)), jnp.int32))["params"]
    cfg = ServingModelConfig.from_model(
        model, prefill_flash=prefill_flash,
        decode_attention=decode_attention)
    weights = extract_serving_weights(params, num_layers)
    if pol is not None and pol.quantize_weights == "int8":
        from ..ops.quant_matmul import quantize_weights as _quantize_w
        weights = _quantize_w(weights)
    cache_cfg = default_cache_config(cfg, num_blocks=num_blocks,
                                     block_size=block_size,
                                     kv_dtype=kv_dtype)
    if ladder is None:
        ladder = BucketLadder.from_flags()
    from ..analysis.flags import flag_int as _flag_int
    from ..analysis.flags import flag_str as _flag_str

    spec_k = speculate_k if speculate_k is not None \
        else _flag_int("APEX_TPU_SERVE_SPECULATE_K")
    draft_weights = draft_cfg = None
    if spec_k > 0:
        if draft == "self":
            # the target proposes for itself: acceptance is exactly
            # 1.0, proving the verify/rollback machinery end to end
            # with the output-identity bar still armed
            draft_weights, draft_cfg = weights, cfg
        elif draft == "narrow":
            draft_model = GPTModel(
                vocab_size=vocab, hidden_size=max(hidden // 2,
                                                  2 * num_heads),
                num_layers=1, num_attention_heads=num_heads,
                max_sequence_length=max_seq, attention_dropout=0.0,
                hidden_dropout=0.0, use_flash=False, dtype=dtype)
            draft_params = jax.jit(draft_model.init)(
                jax.random.PRNGKey(seed + 1),
                jnp.zeros((1, min(8, max_seq)), jnp.int32))["params"]
            draft_cfg = ServingModelConfig.from_model(
                draft_model, prefill_flash=prefill_flash,
                decode_attention=decode_attention)
            draft_weights = extract_serving_weights(draft_params, 1)
        else:
            raise ValueError(f"draft {draft!r} not in "
                             f"('self', 'narrow')")
    ep_width = ep if ep is not None else _flag_int("APEX_TPU_SERVE_EP")
    ep_ctx = None
    if ep_width and ep_width > 0:
        import dataclasses as _dc

        from ..serving import EPContext, expand_moe_weights

        if pol is not None and pol.quantize_weights == "int8":
            raise ValueError(
                "--ep does not compose with the Q8 tier: the int8 "
                "kernel has no expert-stack layout")
        n_exp = moe_experts if moe_experts else 2 * ep_width
        # capacity_factor 8.0 keeps per-rank capacity >= the chunk
        # count at decode's 1-token-per-sequence buckets, so the
        # overlapped exchange engages even on the tiny smoke shapes
        cfg = _dc.replace(
            cfg, num_experts=n_exp, moe_capacity_factor=8.0,
            moe_a2a_chunks=max(1, _flag_int("APEX_TPU_MOE_A2A_CHUNKS")))
        weights = expand_moe_weights(weights, n_exp,
                                     jax.random.PRNGKey(seed + 2))
        ep_ctx = EPContext(cfg, cache_cfg, ep_width)
    if escalation == "auto":
        # serve watchdog policy: a stalled decode snapshots the live
        # engine state then drains cleanly, instead of the training
        # default's ignore (docs/api/resilience.md#serving-resilience)
        escalation = serve_policy()
    monitor = make_smoke_monitor(
        jsonl, sink, tokens_per_step=None, flops_per_step=None,
        stall_timeout=stall_timeout, escalation=escalation,
        watchdog_trace_dir=(os.path.join(trace_dir, "stall")
                            if trace_dir else None),
        run_attrs={"driver": "standalone_gpt.serve_smoke",
                   "requests": num_requests, "max_seq": max_seq,
                   "kv_dtype": cache_cfg.kv_dtype,
                   "block_size": cache_cfg.block_size,
                   "decode_attention": decode_attention,
                   "policy": policy or "none",
                   "ep": ep_width or 0})
    if metrics_port is None:
        _fp = _flag_int("APEX_TPU_METRICS_PORT")
        metrics_port = _fp if _fp > 0 else None
    exporter = metrics_server = None
    if metrics_port is not None:
        from ..monitor.export import MetricsExporter, MetricsServer

        exporter = MetricsExporter()
        metrics_server = MetricsServer(exporter, port=metrics_port,
                                       monitor=monitor)
        metrics_server.start()
        print(f"METRICS http://127.0.0.1:{metrics_server.port}"
              f"/metrics", flush=True)
    if isinstance(fault, str):
        fault = parse_fault(fault)
    journal = None
    if journal_path is None:
        jdir = _flag_str("APEX_TPU_SERVE_JOURNAL_DIR")
        if jdir:
            os.makedirs(jdir, exist_ok=True)
            journal_path = os.path.join(jdir, "serve.journal.jsonl")
    if journal_path is not None:
        journal = RequestJournal(journal_path)
    if supervise and journal is None:
        raise ValueError(
            "supervise=True needs a journal (journal_path or "
            "APEX_TPU_SERVE_JOURNAL_DIR): recovery replays it")
    own_autoresume = False
    if autoresume == "auto":
        autoresume = AutoResume(sink=monitor).install()
        own_autoresume = True
    own_snapshot = False
    if snapshot == "auto":
        # SIGUSR1 (flag-only handler) + the registered file trigger:
        # a wedged serve dumps its live state as one engine_snapshot
        # event at the next tick boundary
        snapshot = SnapshotTrigger.from_flags(
            signum=getattr(signal, "SIGUSR1", None))
        own_snapshot = True
    engine = ServingEngine(weights, cfg, cache_cfg, ladder=ladder,
                           monitor=monitor, autoresume=autoresume,
                           tick_every=tick_every, snapshot=snapshot,
                           ep=ep_ctx, speculate_k=spec_k,
                           draft_weights=draft_weights,
                           draft_cfg=draft_cfg,
                           prefill_chunk=prefill_chunk,
                           prefix_share=prefix_share,
                           deadline_ms=deadline_ms, shed=shed,
                           journal=journal, escalation=escalation,
                           fault=fault, exporter=exporter)
    # mixed-length prompts, deterministic per seed; every request
    # fits the ladder span and the model's position table
    rng = np.random.RandomState(seed)
    span = ladder.max_pages * cache_cfg.block_size
    max_prompt = max(1, min(max_seq, span) - max_new_tokens)
    lengths = [1 + (int(x) % max_prompt)
               for x in rng.randint(1, 10 ** 6, num_requests)]
    prompts = [[int(t) for t in rng.randint(0, vocab, n)]
               for n in lengths]
    before = None
    if fault is not None:
        # the serve-aware hook: crash/stall/signals like the training
        # loop, plus corrupt_journal against the live journal (the
        # reject_alloc kind fires inside the engine's admission path)
        def before(tick, _f=fault):
            _f.before_tick(tick, journal_path=journal_path)
    from ..monitor.events import ThreadExceptionCapture

    thread_cap = ThreadExceptionCapture(monitor).install()
    try:
        with contextlib.ExitStack() as stack:
            san = None
            if sanitize:
                from ..analysis import sanitize as sanitize_ctx

                # every ladder bucket AOT-compiles in warmup(), so the
                # serve holds recompile_budget=0 after the first tick
                san = stack.enter_context(sanitize_ctx(
                    transfer_guard=None, recompile_budget=0,
                    warmup_steps=1))
            engine.warmup()
            if escalation is not None:
                # warmup is not serving: a single AOT compile can
                # outlast a short stall timeout and latch the policy
                # before the first tick ever runs — re-arm it at the
                # traffic boundary (the same per-attempt reset
                # discipline as _run_smoke_loop)
                escalation.reset()
            # submit AFTER warmup so the reported queue-wait/TTFT
            # distributions measure serving, not AOT compile time
            requests = [Request(rid=f"req{i:03d}", prompt=p,
                                max_new_tokens=max_new_tokens)
                        for i, p in enumerate(prompts)]
            after = (lambda i: san.step()) if san else None
            if supervise:
                res = run_serving(
                    engine, requests, journal=journal,
                    max_restarts=max_restarts,
                    backoff_base=backoff_base,
                    monitor=monitor, before_tick=before,
                    after_tick=after)
                summary = res.summary   # restarts set by run_serving
            else:
                for r in requests:
                    engine.submit(r)
                summary = engine.run(before_tick=before,
                                     after_tick=after)
        if trace_dir is not None:
            # one Perfetto lane per request (queued/prefill/decode),
            # written through the PR-7 atomic Chrome writer so the
            # serve loads next to a device trace
            from ..monitor.tracing import write_chrome_trace

            os.makedirs(trace_dir, exist_ok=True)
            write_chrome_trace(
                os.path.join(trace_dir, "serve.chrome.json"),
                engine.metrics.chrome_trace())
    except BaseException as e:
        monitor.event("run", "run_error", step=engine.steps,
                      error=type(e).__name__, message=str(e)[:200])
        raise
    finally:
        try:
            if metrics_server is not None:
                # linger so an external probe can see the drained
                # /healthz (the run() tail published it with
                # draining=True) before the server goes away
                if metrics_linger > 0:
                    time.sleep(metrics_linger)
                metrics_server.stop()
        finally:
            try:
                monitor.close()
            finally:
                try:
                    if journal is not None:
                        journal.close()
                finally:
                    try:
                        if own_snapshot and snapshot is not None:
                            snapshot.close()
                    finally:
                        try:
                            if own_autoresume:
                                autoresume.uninstall()
                        finally:
                            thread_cap.uninstall()
    # a background thread (watchdog heartbeat) that died mid-serve
    # fails the run after teardown instead of vanishing
    thread_cap.raise_first()
    if return_engine:
        return summary, engine
    return summary


# ---------------------------------------------------------------------------
# Fleet serving smoke — multi-replica acceptance path (ISSUE-14)
# ---------------------------------------------------------------------------

def fleet_smoke(num_requests: int = 8, *, replicas: Optional[int] = None,
                tp: Optional[int] = None,
                ep: Optional[int] = None,
                moe_experts: Optional[int] = None,
                disaggregate: Optional[bool] = None,
                policy: Optional[str] = None,
                jsonl_dir: Optional[str] = None,
                vocab: int = 64, hidden: int = 32, num_heads: int = 4,
                num_layers: int = 2, max_seq: int = 64,
                max_new_tokens: int = 4, seed: int = 0,
                dtype=jnp.float32, decode_attention: str = "kernel",
                num_blocks: Optional[int] = None,
                block_size: Optional[int] = None,
                kv_dtype: Optional[str] = None, ladder=None,
                sanitize: bool = False, threads: bool = False,
                swap: bool = False, swap_after: int = 2,
                prefix_share: Optional[bool] = None,
                journal_dir: Optional[str] = None, fault=None,
                fault_replica: str = "r0", max_restarts: int = 3,
                stall_timeout: float = 300.0,
                metrics_port: Optional[int] = None,
                metrics_linger: float = 0.0,
                return_router: bool = False, scheduler=None):
    """Multi-replica serving smoke: N :class:`~apex_tpu.serving.
    ServingEngine` replicas behind the gauge-fed
    :class:`~apex_tpu.serving.FleetRouter` (the ``--serve-fleet``
    acceptance path, tools/ci.sh step 12).

    ``replicas``/``tp``/``disaggregate``/``policy`` default to the
    ``APEX_TPU_SERVE_REPLICAS``/``_TP``/``_DISAGGREGATE``/``_ROUTER``
    flags.  Each replica gets its own engine, KV pool, device (the
    i-th host device, or with ``tp`` its own ``tp``-device slice and
    a :class:`~apex_tpu.serving.TPContext` — head-sharded attention,
    2 psums/layer, greedy output token-identical to single-chip), its
    own JSONL event log (``jsonl_dir/serve-<rid>.jsonl``,
    replica-stamped events) and, with ``journal_dir``, its own crash
    journal — ``fault="crash@K"`` on ``fault_replica`` then recovers
    by crash_reset + replay while the other replicas keep serving.
    ``disaggregate=True`` adds a prefill-role replica streaming
    finished prompt KV into the decode replicas' pools (warm
    admissions, ``prefix_hit_tokens > 0``).  ``swap=True`` performs
    one rolling weight swap (to a freshly initialized model) after
    ``swap_after`` fleet rounds — zero requests lost, zero new
    compiles (the sanitized leg proves both).  ``threads=True`` runs
    one thread per replica (the aggregate-tokens/s scaling mode);
    the default stepped loop is deterministic and supports
    disaggregation and the mid-serve swap.

    ``metrics_port`` (flag: ``APEX_TPU_METRICS_PORT``; explicit
    ``0`` = ephemeral) starts ONE :class:`~apex_tpu.monitor.
    MetricsServer` for the whole fleet: ``/metrics`` carries every
    replica's series under ``replica`` labels plus the
    ``apex_tpu_fleet_*`` aggregates and trend gauges (ISSUE-17),
    ``/healthz`` is ok only when every replica is, ``/varz`` maps
    replica id → snapshot.  ``metrics_linger`` holds the server up
    after the serve for external probes.

    ``scheduler`` (an :class:`apex_tpu.analysis.schedule.
    DeterministicScheduler`) gates the threaded replicas' tick
    boundaries in a seeded permuted order — the race-stress mode.
    A background thread dying mid-serve (``threading.excepthook``)
    is captured, emitted as a ``run_error`` event, and re-raised
    after teardown instead of vanishing.

    Returns the :class:`~apex_tpu.serving.FleetSummary` (with
    ``return_router=True``, ``(summary, router)``)."""
    import numpy as np

    from ..analysis.flags import (flag_bool, flag_int,
                                  flag_str)
    from ..resilience import parse_fault
    from ..serving import (BucketLadder, FleetRouter, Replica, Request,
                           RequestJournal, ServingEngine,
                           ServingModelConfig, TPContext,
                           default_cache_config,
                           extract_serving_weights)

    replicas = replicas if replicas is not None \
        else flag_int("APEX_TPU_SERVE_REPLICAS")
    tp = tp if tp is not None else flag_int("APEX_TPU_SERVE_TP")
    ep = ep if ep is not None else flag_int("APEX_TPU_SERVE_EP")
    if ep and ep > 1 and tp and tp > 1:
        raise ValueError("a replica is tensor-parallel OR expert-"
                         "parallel, not both — pass --tp or --ep")
    disaggregate = disaggregate if disaggregate is not None \
        else flag_bool("APEX_TPU_SERVE_DISAGGREGATE")
    policy = policy if policy is not None \
        else flag_str("APEX_TPU_SERVE_ROUTER")
    if disaggregate:
        prefix_share = True         # the handoff lands through the
        # shared index; colocated replicas may still opt in
    if disaggregate and threads:
        raise ValueError("disaggregation needs the stepped fleet "
                         "loop (threads=False)")

    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=num_layers,
        num_attention_heads=num_heads, max_sequence_length=max_seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=dtype)
    key = jax.random.PRNGKey(seed)
    probe = jnp.zeros((1, min(8, max_seq)), jnp.int32)
    params = jax.jit(model.init)(key, probe)["params"]
    cfg = ServingModelConfig.from_model(
        model, decode_attention=decode_attention)
    weights = extract_serving_weights(params, num_layers)
    if ep and ep > 1:
        import dataclasses as _dc

        from ..serving import expand_moe_weights

        n_exp = moe_experts if moe_experts else 2 * ep
        cfg = _dc.replace(
            cfg, num_experts=n_exp, moe_capacity_factor=8.0,
            moe_a2a_chunks=max(1, flag_int("APEX_TPU_MOE_A2A_CHUNKS")))
        weights = expand_moe_weights(weights, n_exp,
                                     jax.random.PRNGKey(seed + 2))
    swap_weights = None
    if swap:
        # a REAL weight change (fresh init): the swap leg proves the
        # fleet swaps models, not just that the plumbing runs
        swap_params = jax.jit(model.init)(
            jax.random.PRNGKey(seed + 101), probe)["params"]
        swap_weights = extract_serving_weights(swap_params, num_layers)
        if ep and ep > 1:
            from ..serving import expand_moe_weights

            swap_weights = expand_moe_weights(
                swap_weights, cfg.num_experts,
                jax.random.PRNGKey(seed + 2))
    if ladder is None:
        ladder = BucketLadder.from_flags()
    devices = jax.devices()
    if isinstance(fault, str):
        fault = parse_fault(fault)

    def make_cache_cfg():
        return default_cache_config(cfg, num_blocks=num_blocks,
                                    block_size=block_size,
                                    kv_dtype=kv_dtype)

    monitors = []
    members = []
    total = replicas + (1 if disaggregate else 0)
    if tp and tp > 1 and total * tp > len(devices):
        raise ValueError(
            f"{total} replica(s) x tp={tp} needs {total * tp} "
            f"devices, host has {len(devices)}")
    if ep and ep > 1 and total * ep > len(devices):
        raise ValueError(
            f"{total} replica(s) x ep={ep} needs {total * ep} "
            f"devices, host has {len(devices)}")

    if jsonl_dir:
        os.makedirs(jsonl_dir, exist_ok=True)

    def make_member(idx: int, rid: str, role: str) -> Replica:
        monitor = make_smoke_monitor(
            (os.path.join(jsonl_dir, f"serve-{rid}.jsonl")
             if jsonl_dir else None), None,
            tokens_per_step=None, flops_per_step=None,
            stall_timeout=stall_timeout,
            run_attrs={"driver": "standalone_gpt.fleet_smoke",
                       "replica": rid, "role": role,
                       "replicas": replicas, "tp": tp or 0,
                       "ep": ep or 0,
                       "disaggregate": bool(disaggregate)})
        monitors.append(monitor)
        cache_cfg = make_cache_cfg()
        tp_ctx = None
        ep_ctx = None
        device = None
        if tp and tp > 1:
            tp_ctx = TPContext(cfg, cache_cfg, tp,
                               devices=devices[idx * tp:
                                               (idx + 1) * tp])
        elif ep and ep > 1:
            from ..serving import EPContext

            ep_ctx = EPContext(cfg, cache_cfg, ep,
                               devices=devices[idx * ep:
                                               (idx + 1) * ep])
        else:
            device = devices[idx % len(devices)]
        engine = ServingEngine(
            weights, cfg, cache_cfg, ladder=ladder, monitor=monitor,
            prefix_share=prefix_share, tp=tp_ctx, ep=ep_ctx,
            device=device, replica_id=rid)
        journal = None
        if journal_dir:
            os.makedirs(journal_dir, exist_ok=True)
            journal = RequestJournal(
                os.path.join(journal_dir, f"{rid}.journal.jsonl"))
        return Replica(rid, engine, role=role, journal=journal,
                       max_restarts=max_restarts,
                       fault=(fault if rid == fault_replica
                              else None))

    for i in range(replicas):
        members.append(make_member(i, f"r{i}", "serve"))
    if disaggregate:
        members.append(make_member(replicas, "pf0", "prefill"))
    if metrics_port is None:
        _fp = flag_int("APEX_TPU_METRICS_PORT")
        metrics_port = _fp if _fp > 0 else None
    exporter = metrics_server = None
    if metrics_port is not None:
        from ..monitor.export import MetricsExporter, MetricsServer

        exporter = MetricsExporter()
        metrics_server = MetricsServer(exporter, port=metrics_port,
                                       monitor=monitors[0])
        metrics_server.start()
        print(f"METRICS http://127.0.0.1:{metrics_server.port}"
              f"/metrics", flush=True)
    # the router gets replica 0's RAW monitor (pre-stamping): fleet-
    # scope events (request_routed, kv_handoff, fleet_done) carry
    # their own explicit replica attrs and must not inherit a bogus
    # replica="r0" default
    router = FleetRouter(members, policy=policy, monitor=monitors[0],
                         exporter=exporter)

    # deterministic mixed-length prompts with shared-prefix pairs (so
    # sticky routing and the prefix machinery have something to bite)
    rng = np.random.RandomState(seed)
    span = ladder.max_pages * make_cache_cfg().block_size
    max_prompt = max(1, min(max_seq, span) - max_new_tokens)
    prompts = []
    for i in range(num_requests):
        n = 1 + (int(rng.randint(1, 10 ** 6)) % max_prompt)
        prompts.append([int(t) for t in rng.randint(0, vocab, n)])
    requests = [Request(rid=f"req{i:03d}", prompt=p,
                        max_new_tokens=max_new_tokens)
                for i, p in enumerate(prompts)]

    from ..monitor.events import ThreadExceptionCapture

    # the crash event lands in replica 0's JSONL (the fleet-scope
    # log); the explicit replica="fleet" attr keeps it from reading
    # as an r0 failure — the record's `thread` names the real owner
    thread_cap = ThreadExceptionCapture(
        monitors[0] if monitors else None,
        attrs={"replica": "fleet"})
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(thread_cap)
            san = None
            if sanitize:
                from ..analysis import sanitize as sanitize_ctx

                san = stack.enter_context(sanitize_ctx(
                    transfer_guard=None, recompile_budget=0,
                    warmup_steps=1))
            for m in members:
                with m.device_scope():
                    m.engine.warmup()
            if threads:
                summary = router.serve_threaded(requests,
                                                scheduler=scheduler)
            else:
                after = (lambda i: san.step()) if san else None
                summary = router.serve(
                    requests,
                    swap_after=(swap_after if swap else None),
                    swap_weights=swap_weights,
                    before_round=after)
    finally:
        try:
            if metrics_server is not None:
                if metrics_linger > 0:
                    time.sleep(metrics_linger)
                metrics_server.stop()
        finally:
            for m in monitors:
                m.close()
    # a background thread that died mid-serve (captured by the
    # excepthook above, run_error already in the log) fails the run
    # AFTER teardown — it must not vanish into stderr
    thread_cap.raise_first()
    if return_router:
        return summary, router
    return summary


# ---------------------------------------------------------------------------
# Process-isolated fleet (ISSUE-18) — subprocess builder + driver
# ---------------------------------------------------------------------------

def build_fleet_engine(spec_dict: dict) -> dict:
    """Child-side :class:`~apex_tpu.serving.EngineSpec` builder — the
    default entry point a replica subprocess resolves and calls with
    its spec as a plain dict.  Runs entirely IN THE CHILD: model init,
    weight extraction, cache allocation, warmup, the JSONL monitor and
    the crash journal all live here; the supervising parent only ever
    sees the socket.  The model kwargs mirror :func:`fleet_smoke`'s
    member construction, so a process fleet and an in-process fleet
    built from the same seed serve token-identical greedy output.

    Returns ``{"engine", "monitor", "journal", "close"}`` per the
    builder contract.  ``close`` pops the ``jax.default_device`` scope
    that pins this replica's staging to its own device for the life of
    the process (the fleet-scaling discipline from ISSUE-14)."""
    import contextlib as _ctx

    from ..serving import (BucketLadder, RequestJournal,
                           ServingEngine, ServingModelConfig,
                           default_cache_config,
                           extract_serving_weights)

    m = dict(spec_dict.get("model") or {})
    vocab = int(m.get("vocab", 64))
    hidden = int(m.get("hidden", 32))
    num_heads = int(m.get("num_heads", 4))
    num_layers = int(m.get("num_layers", 2))
    max_seq = int(m.get("max_seq", 64))
    seed = int(m.get("seed", 0))
    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=num_layers,
        num_attention_heads=num_heads, max_sequence_length=max_seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=jnp.float32)
    key = jax.random.PRNGKey(seed)
    probe = jnp.zeros((1, min(8, max_seq)), jnp.int32)
    params = jax.jit(model.init)(key, probe)["params"]
    cfg = ServingModelConfig.from_model(
        model, decode_attention=m.get("decode_attention", "kernel"))
    weights = extract_serving_weights(params, num_layers)
    cache_cfg = default_cache_config(
        cfg, num_blocks=m.get("num_blocks"),
        block_size=m.get("block_size"),
        kv_dtype=m.get("kv_dtype"))
    devices = jax.devices()
    di = spec_dict.get("device_index")
    device = (devices[int(di) % len(devices)]
              if di is not None else None)
    rid = str(spec_dict["replica_id"])
    monitor = make_smoke_monitor(
        spec_dict.get("jsonl_path"), None, tokens_per_step=None,
        flops_per_step=None,
        stall_timeout=float(m.get("stall_timeout", 300.0)),
        run_attrs={"driver": "standalone_gpt.build_fleet_engine",
                   "replica": rid, "role": spec_dict.get("role"),
                   "pid": os.getpid()})
    journal = (RequestJournal(spec_dict["journal_path"])
               if spec_dict.get("journal_path") else None)
    scope = _ctx.ExitStack()
    if device is not None:
        scope.enter_context(jax.default_device(device))
    engine = ServingEngine(
        weights, cfg, cache_cfg,
        ladder=BucketLadder.from_flags(), monitor=monitor,
        prefix_share=m.get("prefix_share"), device=device,
        replica_id=rid, journal=journal)
    engine.warmup()
    return {"engine": engine, "monitor": monitor,
            "journal": journal, "close": scope.close}


def fleet_procs_smoke(num_requests: int = 8, *, replicas: int = 2,
                      disaggregate: bool = False,
                      jsonl_dir: Optional[str] = None,
                      journal_dir: Optional[str] = None,
                      vocab: int = 64, hidden: int = 32,
                      num_heads: int = 4, num_layers: int = 2,
                      max_seq: int = 64, max_new_tokens: int = 4,
                      seed: int = 0,
                      decode_attention: str = "kernel",
                      num_blocks: Optional[int] = None,
                      block_size: Optional[int] = None,
                      kv_dtype: Optional[str] = None,
                      prefix_share: Optional[bool] = None,
                      fault=None, fault_replica: str = "r0",
                      max_restarts: int = 3,
                      autoscale: Optional[str] = None,
                      qos=None,
                      metrics_port: Optional[int] = None,
                      freerun: bool = False,
                      stall_timeout: float = 300.0,
                      tick_seed: int = 0,
                      rpc_timeout_s: Optional[float] = None,
                      poll_timeout_s: Optional[float] = None,
                      heartbeat_misses: Optional[int] = None,
                      return_fleet: bool = False):
    """Process-isolated fleet smoke (``--serve-fleet --procs``,
    tools/ci.sh step 16): ``replicas`` supervised subprocesses, each
    a full :func:`build_fleet_engine` replica on its own device,
    driven over local sockets by :class:`~apex_tpu.serving.
    ProcessFleet` — heartbeat liveness, ``fault="kill9@K"`` SIGKILL
    drills recovered by journal replay (fleet digest token-identical
    to an uninterrupted run), ``fault="rpc_timeout@K"`` degraded
    gauge polls, disaggregated prefill KV handoff over the socket,
    and ``autoscale="MIN:MAX"`` queue-depth-trend scaling with
    drain-then-reap scale-down.  ``freerun=True`` posts one ``run``
    RPC per replica instead of the stepped round loop (the scaling
    bench mode).  Returns the :class:`~apex_tpu.serving.
    ProcessFleetSummary` (with ``return_fleet=True``, ``(summary,
    fleet)`` — the fleet is already closed)."""
    import numpy as np

    from ..serving import (AutoscalePolicy, BucketLadder, EngineSpec,
                           ProcessFleet, ServingModelConfig,
                           default_cache_config)

    if jsonl_dir:
        os.makedirs(jsonl_dir, exist_ok=True)
    if journal_dir is None:
        # the kill-9 drill is only recoverable through the on-disk
        # journal, so a journal is not optional in process mode
        journal_dir = tempfile.mkdtemp(prefix="apexcp-journal-")
    os.makedirs(journal_dir, exist_ok=True)

    model_kwargs = {
        "vocab": vocab, "hidden": hidden, "num_heads": num_heads,
        "num_layers": num_layers, "max_seq": max_seq, "seed": seed,
        "decode_attention": decode_attention,
        "num_blocks": num_blocks, "block_size": block_size,
        "kv_dtype": kv_dtype, "stall_timeout": stall_timeout,
        "prefix_share": (True if disaggregate else prefix_share),
    }

    def make_spec(rid: str, idx: int, role: str = "serve"
                  ) -> EngineSpec:
        return EngineSpec(
            replica_id=rid, role=role, model=model_kwargs,
            device_index=idx,
            jsonl_path=(os.path.join(jsonl_dir,
                                     f"serve-{rid}.jsonl")
                        if jsonl_dir else None),
            journal_path=os.path.join(journal_dir,
                                      f"{rid}.journal.jsonl"))

    specs = [make_spec(f"r{i}", i) for i in range(replicas)]
    if disaggregate:
        specs.append(make_spec("pf0", replicas, "prefill"))

    policy = None
    if autoscale:
        lo, _, hi = str(autoscale).partition(":")
        policy = AutoscalePolicy(min_replicas=int(lo),
                                 max_replicas=int(hi or lo))

    # the same deterministic prompt mix as fleet_smoke — cfg/ladder
    # construction here is host-side math only (no device arrays in
    # the parent)
    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=num_layers,
        num_attention_heads=num_heads, max_sequence_length=max_seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=jnp.float32)
    cfg = ServingModelConfig.from_model(
        model, decode_attention=decode_attention)
    cache_cfg = default_cache_config(cfg, num_blocks=num_blocks,
                                     block_size=block_size,
                                     kv_dtype=kv_dtype)
    ladder = BucketLadder.from_flags()
    rng = np.random.RandomState(seed)
    span = ladder.max_pages * cache_cfg.block_size
    max_prompt = max(1, min(max_seq, span) - max_new_tokens)
    requests = []
    for i in range(num_requests):
        n = 1 + (int(rng.randint(1, 10 ** 6)) % max_prompt)
        requests.append({
            "rid": f"req{i:03d}",
            "prompt": [int(t) for t in rng.randint(0, vocab, n)],
            "max_new_tokens": max_new_tokens})

    fleet = ProcessFleet(
        specs,
        jsonl_path=(os.path.join(jsonl_dir, "supervisor.jsonl")
                    if jsonl_dir else None),
        qos=qos, autoscale=policy,
        spec_factory=make_spec,
        metrics_port=metrics_port, fault=fault,
        fault_replica=fault_replica, max_restarts=max_restarts,
        rpc_timeout_s=rpc_timeout_s, poll_timeout_s=poll_timeout_s,
        heartbeat_misses=heartbeat_misses, tick_seed=tick_seed)
    with fleet:
        summary = fleet.serve(requests, freerun=freerun)
    if return_fleet:
        return summary, fleet
    return summary


def add_resilience_cli(p) -> None:
    """The shared GPT/BERT smoke-driver resilience flags."""
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory; enables periodic saves, "
                        "auto-resume from the latest valid step, and "
                        "SIGTERM-safe exit with a CLEAN_EXIT.json "
                        "marker")
    p.add_argument("--ckpt-every", type=int, default=1,
                   help="save every N steps (default 1)")
    p.add_argument("--no-resume", action="store_true",
                   help="start from step 0 even if checkpoints exist")
    p.add_argument("--fault", default=None,
                   help="deterministic fault spec, e.g. 'sigterm@4', "
                        "'crash@3', 'nan@2,crash@5', 'stall@1:0.5' "
                        "(see apex_tpu.resilience.faults)")


def _main(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="Monitored GPT smoke train loop (CPU-friendly); "
                    "writes an apex_tpu.monitor JSONL event log. "
                    "With --ckpt-dir the loop is preemption-safe: "
                    "kill it (--fault sigterm@K or a real SIGTERM) and "
                    "re-run the same command to resume.")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--jsonl", default=None,
                   help="event-log path (default: in-memory only)")
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--stall-timeout", type=float, default=300.0)
    p.add_argument("--sanitize", action="store_true",
                   help="run under apex_tpu.analysis.sanitize(): fail "
                        "if the train step recompiles after warmup "
                        "(with --telemetry-drain-every also fail on "
                        "ANY per-step implicit device->host transfer)")
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="wall-time attribution: per-step waterfall + "
                        "host spans into the event log, "
                        "DIR/trace.chrome.json for Perfetto, and the "
                        "APEX_TPU_TRACE_* capture triggers")
    p.add_argument("--telemetry-drain-every", type=int, default=None,
                   metavar="K",
                   help="deferred telemetry: accumulate per-step "
                        "scalars in a device ring, drain every K "
                        "steps (zero per-step host transfers); "
                        "default: APEX_TPU_TELEMETRY_DRAIN_EVERY "
                        "(0 = classic synchronous readback)")
    p.add_argument("--scan-steps", type=int, default=None, metavar="K",
                   help="batched-step scan driver: K train steps per "
                        "jit call (lax.scan; amp state + telemetry "
                        "ring in the donated carry, AOT-compiled "
                        "windows, drains/checkpoints on K-step "
                        "edges); default: APEX_TPU_SCAN_STEPS "
                        "(0 = classic per-step loop)")
    p.add_argument("--serve", action="store_true",
                   help="run the continuous-batching serving smoke "
                        "instead of the train loop: mixed-length "
                        "requests through the apex_tpu.serving "
                        "engine (prefill = flash fwd kernel, decode "
                        "= paged flash-decode kernel), tokens/s and "
                        "p50/p99 per-token latency plus TTFT/queue-"
                        "wait percentiles reported; with --sanitize "
                        "proves one compile per ladder bucket; "
                        "--fault sigterm@K proves the clean drain; "
                        "with --trace DIR also writes per-request "
                        "Perfetto lanes to DIR/serve.chrome.json")
    p.add_argument("--requests", type=int, default=6,
                   help="(--serve) number of requests to serve")
    p.add_argument("--new-tokens", type=int, default=6,
                   help="(--serve) tokens generated per request")
    p.add_argument("--serve-max-seq", type=int, default=64,
                   help="(--serve) model position-table length")
    p.add_argument("--decode-reference", action="store_true",
                   help="(--serve) dense full-gather decode instead "
                        "of the paged kernel (the naive baseline)")
    p.add_argument("--policy", default=None, choices=("O5", "Q8"),
                   help="(--serve) amp serving tier: O5 casts the "
                        "model to bf16; Q8 additionally quantizes "
                        "every matmul weight to per-channel int8 "
                        "(weight-only, fp32 accumulation) — the "
                        "quantized decode smoke")
    p.add_argument("--speculate-k", type=int, default=None,
                   metavar="K",
                   help="(--serve) speculative decoding: a draft "
                        "model proposes K tokens per tick, the "
                        "target scores all of them in one paged "
                        "multi-token call; greedy-match acceptance "
                        "keeps output token-identical to plain "
                        "greedy decode (default: "
                        "APEX_TPU_SERVE_SPECULATE_K)")
    p.add_argument("--draft", choices=("self", "narrow"),
                   default="self",
                   help="(--serve --speculate-k) draft model: "
                        "'self' reuses the target (acceptance 1.0 "
                        "ceiling), 'narrow' a 1-layer half-width "
                        "GPT (rollback stress)")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   metavar="N",
                   help="(--serve) chunked prefill: split prompt "
                        "admission into N-token chunks interleaved "
                        "one per tick with decode (default: "
                        "APEX_TPU_SERVE_PREFILL_CHUNK)")
    p.add_argument("--prefix-share", action="store_true",
                   default=None,
                   help="(--serve) copy-on-write prompt-prefix "
                        "sharing: warm prefixes map shared KV pages "
                        "instead of re-prefilling (default: "
                        "APEX_TPU_SERVE_PREFIX_SHARE)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="(--serve) default request deadline in ms "
                        "(submit -> last token); queued requests past "
                        "it expire terminal deadline_exceeded, "
                        "running ones are evicted terminal deadline "
                        "(default: APEX_TPU_SERVE_DEADLINE_MS)")
    p.add_argument("--shed-pool-hw", type=float, default=None,
                   help="(--serve) load-shedding high-water mark on "
                        "pool pressure, fraction (default: "
                        "APEX_TPU_SERVE_SHED_POOL_HW; 0 disables)")
    p.add_argument("--shed-queue-hw", type=int, default=None,
                   help="(--serve) load-shedding high-water mark on "
                        "the admission backlog (default: "
                        "APEX_TPU_SERVE_SHED_QUEUE_HW; 0 disables)")
    p.add_argument("--journal", default=None, metavar="PATH",
                   help="(--serve) crash-safe request journal JSONL "
                        "(submit/progress/terminal transitions; "
                        "default: APEX_TPU_SERVE_JOURNAL_DIR/"
                        "serve.journal.jsonl when that flag is set)")
    p.add_argument("--supervise", action="store_true",
                   help="(--serve) run the engine under the "
                        "serving supervisor: bounded-backoff "
                        "restarts, journal replay of every "
                        "non-terminal request after a crash "
                        "(requires --journal); --fault crash@K "
                        "recovers instead of dying")
    p.add_argument("--max-restarts", type=int, default=3,
                   help="(--serve --supervise) restart budget "
                        "(default 3)")
    p.add_argument("--serve-fleet", action="store_true",
                   help="multi-replica serving smoke: N engines "
                        "behind the gauge-fed FleetRouter "
                        "(apex_tpu.serving.fleet) — per-replica KV "
                        "pools/devices/JSONL logs, sticky warm "
                        "routing, optional TP decode, disaggregated "
                        "prefill/decode, and a rolling weight swap; "
                        "prints a FLEET_DONE row")
    p.add_argument("--replicas", type=int, default=None,
                   help="(--serve-fleet) serve-role replica count "
                        "(default: APEX_TPU_SERVE_REPLICAS)")
    p.add_argument("--tp", type=int, default=None,
                   help="(--serve-fleet) tensor-parallel width per "
                        "replica; each replica takes its own "
                        "TP-device slice (default: "
                        "APEX_TPU_SERVE_TP; 0 = single-chip)")
    p.add_argument("--ep", type=int, default=None,
                   help="(--serve / --serve-fleet) expert-parallel "
                        "width: expand the "
                        "MLPs to a Switch MoE and shard the expert "
                        "stacks over this many devices (default: "
                        "APEX_TPU_SERVE_EP; 0 = single-chip)")
    p.add_argument("--moe-experts", type=int, default=None,
                   help="(--serve --ep) expert count for the MoE "
                        "expansion (default: 2*ep; must divide by "
                        "ep)")
    p.add_argument("--disaggregate", action="store_true",
                   default=None,
                   help="(--serve-fleet) add a prefill-role replica "
                        "that streams finished prompt KV into the "
                        "decode replicas' pools (warm admissions; "
                        "default: APEX_TPU_SERVE_DISAGGREGATE)")
    p.add_argument("--router-policy", default=None,
                   choices=("gauges", "round_robin"),
                   help="(--serve-fleet) submission policy "
                        "(default: APEX_TPU_SERVE_ROUTER)")
    p.add_argument("--swap", action="store_true",
                   help="(--serve-fleet) perform one rolling weight "
                        "swap (to a freshly initialized model) "
                        "mid-serve — zero lost requests, zero new "
                        "compiles")
    p.add_argument("--fleet-threads", action="store_true",
                   help="(--serve-fleet) one thread per replica "
                        "(the aggregate tokens/s scaling mode); "
                        "default is the deterministic stepped loop")
    p.add_argument("--procs", action="store_true",
                   help="(--serve-fleet) process-isolated fleet "
                        "(ISSUE-18): each replica is a supervised "
                        "SUBPROCESS on its own device, driven over "
                        "local sockets by the control plane — "
                        "heartbeat liveness, kill-9 restart with "
                        "journal replay, socket KV handoff; "
                        "--fleet-threads selects the freerun drive "
                        "mode (one run RPC per replica) instead of "
                        "the stepped round loop; prints a "
                        "FLEETP_DONE row")
    p.add_argument("--autoscale", default=None, metavar="MIN:MAX",
                   help="(--procs) autoscale the serve-replica count "
                        "between MIN and MAX from the fleet "
                        "aggregator's queue-depth trend (scale-up on "
                        "backlog, drain-then-reap scale-down); the "
                        "autoscale event trace lands in the "
                        "supervisor JSONL")
    p.add_argument("--jsonl-dir", default=None, metavar="DIR",
                   help="(--serve-fleet) per-replica event logs "
                        "DIR/serve-<rid>.jsonl (replica-stamped; "
                        "aggregate with trace_check --serve "
                        "DIR/serve-*.jsonl)")
    p.add_argument("--journal-dir", default=None, metavar="DIR",
                   help="(--serve-fleet) per-replica crash journals "
                        "DIR/<rid>.journal.jsonl; with --fault "
                        "crash@K the faulted replica recovers by "
                        "journal replay while the rest keep serving")
    p.add_argument("--fleet-hidden", type=int, default=32,
                   help="(--serve-fleet) model hidden size — the "
                        "bench scaling legs use a compute-heavier "
                        "shape than the CI smoke default")
    p.add_argument("--fleet-layers", type=int, default=2,
                   help="(--serve-fleet) model layer count")
    p.add_argument("--fleet-vocab", type=int, default=64,
                   help="(--serve-fleet) model vocab size")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="(--serve / --serve-fleet) start the live "
                        "metrics plane on this port: /metrics "
                        "(Prometheus text exposition), /healthz "
                        "(drain/shed/SLO aware), /varz (engine "
                        "snapshot JSON).  0 = ephemeral port "
                        "(printed as a METRICS line); default: "
                        "APEX_TPU_METRICS_PORT (0 there = off)")
    p.add_argument("--metrics-linger", type=float, default=0.0,
                   metavar="SEC",
                   help="(--metrics-port) keep the metrics server "
                        "up SEC seconds after the drain so an "
                        "external probe can observe the drained "
                        "/healthz before teardown")
    add_resilience_cli(p)
    args = p.parse_args(argv)
    if args.serve_fleet and args.procs:
        s = fleet_procs_smoke(
            args.requests,
            replicas=(args.replicas if args.replicas is not None
                      else 2),
            disaggregate=bool(args.disaggregate),
            jsonl_dir=args.jsonl_dir, journal_dir=args.journal_dir,
            max_new_tokens=args.new_tokens,
            max_seq=args.serve_max_seq, hidden=args.fleet_hidden,
            num_layers=args.fleet_layers, vocab=args.fleet_vocab,
            decode_attention=("reference" if args.decode_reference
                              else "kernel"),
            fault=args.fault, max_restarts=args.max_restarts,
            autoscale=args.autoscale,
            metrics_port=args.metrics_port,
            freerun=args.fleet_threads,
            stall_timeout=args.stall_timeout)
        print(f"FLEETP_DONE replicas={s.replicas} "
              f"prefill_replicas={s.prefill_replicas} "
              f"offered={s.offered} "
              f"submitted={s.submitted} "
              f"shed_admission={s.shed_admission} "
              f"rejected={s.rejected} "
              f"done={s.requests_done} "
              f"lost={s.lost_requests} "
              f"tokens={s.tokens_generated} "
              f"tokens_s={s.tokens_per_sec} "
              f"rounds={s.rounds} "
              f"restarts={s.restarts} "
              f"rpc_timeouts={s.rpc_timeouts} "
              f"handoffs={s.handoffs} "
              f"handoff_retries={s.handoff_retries} "
              f"autoscale_ups={s.autoscale_ups} "
              f"autoscale_downs={s.autoscale_downs} "
              f"replayed={s.replayed_requests} "
              f"digest={s.digest} "
              f"freerun={int(s.freerun)}"
              + (f" jsonl_dir={args.jsonl_dir}"
                 if args.jsonl_dir else ""))
        return
    if args.serve_fleet:
        s = fleet_smoke(
            args.requests, replicas=args.replicas, tp=args.tp,
            ep=args.ep, moe_experts=args.moe_experts,
            disaggregate=args.disaggregate,
            policy=args.router_policy, jsonl_dir=args.jsonl_dir,
            max_new_tokens=args.new_tokens,
            max_seq=args.serve_max_seq,
            hidden=args.fleet_hidden, num_layers=args.fleet_layers,
            vocab=args.fleet_vocab,
            decode_attention=("reference" if args.decode_reference
                              else "kernel"),
            sanitize=args.sanitize, threads=args.fleet_threads,
            swap=args.swap, journal_dir=args.journal_dir,
            fault=args.fault, max_restarts=args.max_restarts,
            stall_timeout=args.stall_timeout,
            metrics_port=args.metrics_port,
            metrics_linger=args.metrics_linger)
        print(f"FLEET_DONE replicas={s.replicas} "
              f"prefill_replicas={s.prefill_replicas} "
              f"policy={s.router_policy} "
              f"submitted={s.requests_submitted} "
              f"done={s.requests_done} "
              f"preempted={s.requests_preempted} "
              f"lost={s.lost_requests} "
              f"tokens={s.tokens_generated} "
              f"tokens_s={s.tokens_per_sec} "
              f"sum_decode_tokens_s={s.sum_decode_tokens_per_sec} "
              f"swaps={s.swaps} handoffs={s.handoffs} "
              f"warm_admissions={s.warm_prefix_admissions} "
              f"prefix_hit_tokens={s.prefix_hit_tokens} "
              f"sticky_routes={s.sticky_routes} "
              f"replayed={s.replayed_requests} "
              f"restarts={s.restarts} "
              f"ttft_p50_ms={s.ttft_p50_ms} "
              f"ttft_p99_ms={s.ttft_p99_ms} "
              f"threaded={int(s.threaded)}"
              + (f" jsonl_dir={args.jsonl_dir}"
                 if args.jsonl_dir else ""))
        return
    if args.serve:
        shed = None
        if args.shed_pool_hw is not None \
                or args.shed_queue_hw is not None:
            from ..analysis.flags import flag_float, flag_int
            from ..serving import ShedPolicy

            # each CLI mark overrides only ITSELF; the other keeps its
            # APEX_TPU_SERVE_SHED_* default as the help text promises
            shed = ShedPolicy(
                pool_hw=(args.shed_pool_hw
                         if args.shed_pool_hw is not None else
                         flag_float("APEX_TPU_SERVE_SHED_POOL_HW")),
                queue_hw=(args.shed_queue_hw
                          if args.shed_queue_hw is not None else
                          flag_int("APEX_TPU_SERVE_SHED_QUEUE_HW")))
        s, eng = serve_smoke(
            args.requests, jsonl=args.jsonl, sanitize=args.sanitize,
            max_new_tokens=args.new_tokens,
            max_seq=args.serve_max_seq, policy=args.policy,
            decode_attention=("reference" if args.decode_reference
                              else "kernel"),
            stall_timeout=args.stall_timeout, fault=args.fault,
            trace_dir=args.trace, speculate_k=args.speculate_k,
            prefill_chunk=args.prefill_chunk,
            prefix_share=args.prefix_share, draft=args.draft,
            deadline_ms=args.deadline_ms, shed=shed,
            journal_path=args.journal, supervise=args.supervise,
            max_restarts=args.max_restarts,
            metrics_port=args.metrics_port,
            metrics_linger=args.metrics_linger,
            ep=args.ep, moe_experts=args.moe_experts,
            return_engine=True)
        spec = "" if s.spec_accept_rate is None else (
            f" spec_accept_rate={s.spec_accept_rate}"
            f" spec_proposed={s.spec_tokens_proposed}")
        share = "" if not (s.warm_prefix_admissions
                           or s.shared_blocks_hw) else (
            f" warm_admissions={s.warm_prefix_admissions}"
            f" prefix_hit_tokens={s.prefix_hit_tokens}"
            f" shared_blocks_hw={s.shared_blocks_hw}"
            f" cow_copies={s.cow_copies}")
        chunks = f" prefill_chunks={s.prefill_chunks}" \
            if s.prefill_chunks else ""
        resil = ""
        if args.supervise or s.replayed_requests:
            resil += (f" restarts={s.restarts}"
                      f" replayed={s.replayed_requests}")
        if s.requests_deadline:
            resil += f" deadline={s.requests_deadline}"
        if s.requests_shed:
            resil += (f" shed={s.requests_shed}"
                      f" shed_engagements={s.shed_engagements}")
        if s.spec_disabled:
            resil += " spec_disabled=1"
        if s.slo_burn_episodes or s.slo_burning:
            resil += (f" slo_burns={s.slo_burn_episodes}"
                      f" slo_recoveries={s.slo_recoveries}"
                      f" slo_burning={','.join(s.slo_burning) or '-'}")
        print(f"SERVE_DONE requests={s.requests_done} "
              f"preempted={s.requests_preempted} "
              f"tokens={s.tokens_generated} "
              f"tokens_s={s.tokens_per_sec} "
              f"p50_ms={s.latency_p50_ms} p99_ms={s.latency_p99_ms} "
              f"ttft_p50_ms={s.ttft_p50_ms} "
              f"ttft_p99_ms={s.ttft_p99_ms} "
              f"queue_wait_p99_ms={s.queue_wait_p99_ms} "
              f"steps={s.decode_steps} "
              f"compiles={len(s.compiles)} "
              f"drained={int(s.drained)}"
              f"{spec}{share}{chunks}{resil} "
              f"digest={eng.tokens_digest()}"
              + (f" jsonl={args.jsonl}" if args.jsonl else ""))
        return
    loss, _, _, done = train_smoke(
        steps=args.steps, jsonl=args.jsonl, opt_level=args.opt_level,
        stall_timeout=args.stall_timeout, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, resume=not args.no_resume,
        fault=args.fault, return_state=True, sanitize=args.sanitize,
        trace_dir=args.trace,
        drain_every=args.telemetry_drain_every,
        scan_steps=args.scan_steps)
    print(f"SMOKE_DONE steps_done={done}"
          + (f" loss={loss:.4f}" if loss is not None else "")
          + (f" jsonl={args.jsonl}" if args.jsonl else ""))


if __name__ == "__main__":
    _main()
