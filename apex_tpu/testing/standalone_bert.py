"""Standalone BERT test model.

Parity surface for ``apex/transformer/testing/standalone_bert.py:10-223``:
bidirectional (padding-mask) transformer, token-type embeddings, pooler,
``BertLMHead`` (dense+gelu+LN then tied-embedding logits with its own
bias), optional binary (NSP) head, vocab-parallel masked-LM loss.  Built
from the same library blocks as the GPT model
(:mod:`apex_tpu.testing.standalone_gpt`).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..normalization import FusedLayerNorm
from ..transformer.enums import AttnMaskType
from ..transformer.layers import ParallelTransformer
from ..transformer.tensor_parallel import vocab_parallel_cross_entropy
from .standalone_gpt import Dtype, GPTEmbedding

Array = jnp.ndarray


def bert_extended_attention_mask(attention_mask: Array) -> Array:
    """(b, s) 1=real/0=pad -> (b, 1, s, s) boolean, True = masked out
    (ref: standalone_bert.py:10-24 — outer product then ``< 0.5``)."""
    b1s = attention_mask[:, None, :]
    bs1 = attention_mask[:, :, None]
    bss = b1s * bs1
    return (bss[:, None, :, :] < 0.5)


def bert_position_ids(token_ids: Array) -> Array:
    """ref: standalone_bert.py:26-33."""
    s = token_ids.shape[1]
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                            token_ids.shape)


class BertEmbedding(GPTEmbedding):
    """GPT embedding + token-type embeddings
    (ref: BertModel num_tokentypes=2)."""

    num_tokentypes: int = 2

    def setup(self):
        super().setup()
        if self.num_tokentypes > 0:
            self.tokentype_embeddings = nn.Embed(
                self.num_tokentypes, self.hidden_size,
                embedding_init=nn.initializers.normal(stddev=0.02),
                dtype=self.dtype, name="tokentype_embeddings")

    def __call__(self, tokens, tokentype_ids=None,
                 deterministic: bool = True):
        h = super().__call__(tokens, deterministic)
        if tokentype_ids is not None and self.num_tokentypes > 0:
            h = h + self.tokentype_embeddings(tokentype_ids)
        return h


class Pooler(nn.Module):
    """[CLS] pooler: dense+tanh over position 0 (Megatron pooler)."""

    hidden_size: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden):  # (b, s, h)
        x = hidden[:, 0]
        x = nn.Dense(self.hidden_size, dtype=self.dtype,
                     name="dense")(x)
        return jnp.tanh(x)


class BertLMHead(nn.Module):
    """Masked-LM head (ref: standalone_bert.py:35-74): dense + gelu +
    LayerNorm, then logits against the (tied) word-embedding matrix with
    a learned per-vocab bias."""

    hidden_size: int
    vocab_size: int
    layernorm_epsilon: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, hidden, attend_fn):
        x = nn.Dense(self.hidden_size, dtype=self.dtype, name="dense")(
            hidden)
        x = jax.nn.gelu(x)
        x = FusedLayerNorm(self.hidden_size, eps=self.layernorm_epsilon,
                           name="layernorm")(x).astype(self.dtype)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.vocab_size,), jnp.float32)
        return attend_fn(x) + bias


class BertModel(nn.Module):
    """ref: standalone_bert.py:101-213."""

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_attention_heads: int
    max_sequence_length: int
    num_tokentypes: int = 2
    add_binary_head: bool = True
    attention_dropout: float = 0.1
    hidden_dropout: float = 0.1
    checkpoint_activations: bool = False
    # use_flash routes the (b, s) padding mask through the flash
    # kernel's kv_mask path (no [b, h, s, s] score materialization) —
    # a capability the reference's FMHA lacks; False keeps the
    # reference-shaped FusedScaleMaskSoftmax path.
    use_flash: bool = False
    dtype: Dtype = jnp.float32
    axis_name: Optional[str] = None

    def setup(self):
        self.embedding = BertEmbedding(
            self.vocab_size, self.hidden_size, self.max_sequence_length,
            embedding_dropout=self.hidden_dropout,
            num_tokentypes=self.num_tokentypes, dtype=self.dtype,
            axis_name=self.axis_name, name="embedding")
        self.transformer = ParallelTransformer(
            num_layers=self.num_layers, hidden_size=self.hidden_size,
            num_attention_heads=self.num_attention_heads,
            attn_mask_type=AttnMaskType.padding,
            attention_dropout=self.attention_dropout,
            hidden_dropout=self.hidden_dropout, use_flash=self.use_flash,
            checkpoint_activations=self.checkpoint_activations,
            dtype=self.dtype, axis_name=self.axis_name,
            name="transformer")
        self.lm_head = BertLMHead(
            self.hidden_size, self.vocab_size, dtype=self.dtype,
            name="lm_head")
        if self.add_binary_head:
            self.pooler = Pooler(self.hidden_size, dtype=self.dtype,
                                 name="pooler")
            self.binary_head = nn.Dense(2, dtype=jnp.float32,
                                        name="binary_head")

    def __call__(self, tokens, attention_mask, tokentype_ids=None,
                 lm_labels=None, deterministic: bool = True):
        """Returns ``(lm_logits_or_loss, binary_logits)``
        (ref: forward :148-175 + post_language_model_processing
        :76-99)."""
        h = self.embedding(tokens, tokentype_ids, deterministic)
        if self.use_flash:
            # the (b, s) mask rides the flash kernel's kv_mask lane
            h = self.transformer(h, None, deterministic,
                                 key_padding_mask=attention_mask)
        else:
            ext_mask = bert_extended_attention_mask(
                attention_mask.astype(jnp.float32))
            h = self.transformer(h, ext_mask, deterministic)

        # both heads and the LM loss under the scope a device trace
        # reads them by (``head_loss_ms.train``)
        with jax.named_scope("apex.head_loss"):
            binary_logits = None
            if self.add_binary_head:
                binary_logits = self.binary_head(
                    self.pooler(h).astype(jnp.float32))

            lm_logits = self.lm_head(h, self.embedding.attend)
            if lm_labels is None:
                return lm_logits, binary_logits
            if self.axis_name is not None:
                lm_loss = vocab_parallel_cross_entropy(
                    lm_logits.astype(jnp.float32), lm_labels,
                    axis_name=self.axis_name)
            else:
                # fused CE: the plain logsumexp/take pair feeds the
                # same fp32 view to two consumers, materializing an
                # fp32 copy of the (tokens, vocab) logits (measured
                # 9.2 ms/step of convert+reduce at BERT-large's 30k
                # vocab); the custom-VJP loss keeps single-consumer
                # fp32 views in fwd AND bwd.
                from ..contrib.xentropy import softmax_cross_entropy_loss

                # 3-D logits go straight in (the loss broadcasts
                # over leading dims) — a flatten/reshape round-trip
                # materialized a copy of the 0.5 GB logits
                lm_loss = softmax_cross_entropy_loss(
                    lm_logits, lm_labels, half_to_float=True)
        return lm_loss, binary_logits


class BertSmokeSetup(NamedTuple):
    """Everything the BERT smoke train step needs, built once — the
    BERT sibling of :class:`.standalone_gpt.SmokeSetup`; shared by
    :func:`train_smoke` and the hlo-auditor entry registry."""

    model: Any
    tokens: jnp.ndarray
    mask: jnp.ndarray
    labels: jnp.ndarray
    nsp: jnp.ndarray
    params: Any
    amp_opt: Any
    amp_state: Any
    n_params: int


def make_smoke_setup(*, vocab: int = 64, hidden: int = 32,
                     num_heads: int = 4, num_layers: int = 2,
                     batch: int = 4, seq: int = 16,
                     opt_level: str = "O2", lr: float = 1e-3,
                     seed: int = 0, dtype=jnp.float32,
                     pipeline: Optional[bool] = None) -> BertSmokeSetup:
    from .. import amp
    from ..optimizers import fused_adam

    model = BertModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=num_layers,
        num_attention_heads=num_heads, max_sequence_length=seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=dtype)
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(jax.random.fold_in(key, 1),
                                (batch, seq), 0, vocab)
    mask = jnp.ones((batch, seq), jnp.int32)
    labels = jnp.roll(tokens, -1, -1)
    nsp = jax.random.randint(jax.random.fold_in(key, 2), (batch,), 0, 2)
    variables = jax.jit(model.init)(key, tokens, mask)
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(variables["params"]))
    params, amp_opt, amp_state = amp.initialize(
        variables["params"], fused_adam(lr), opt_level=opt_level,
        pipeline=pipeline)
    return BertSmokeSetup(model, tokens, mask, labels, nsp, params,
                          amp_opt, amp_state, int(n_params))


def make_step_fn(setup: BertSmokeSetup):
    """The raw (unjitted) BERT smoke train step — the single build
    site the jitted wrappers close over (see
    :func:`.standalone_gpt.make_step_fn`)."""
    from ..transformer.pipeline_parallel.utils import param_l2_norm

    model, tokens, mask = setup.model, setup.tokens, setup.mask
    labels, nsp, amp_opt = setup.labels, setup.nsp, setup.amp_opt

    def _step(params, amp_state):
        def loss_fn(p):
            from ..contrib.xentropy import softmax_cross_entropy_loss

            lm_loss, bin_logits = model.apply(
                {"params": p}, tokens, mask, lm_labels=labels)
            with jax.named_scope("apex.head_loss"):
                nsp_loss = jnp.mean(softmax_cross_entropy_loss(
                    bin_logits, nsp, half_to_float=True))
                loss = jnp.mean(lm_loss) + nsp_loss
            return amp_opt.scale_loss(loss, amp_state), loss

        grads, loss = jax.grad(loss_fn, has_aux=True)(params)
        new_params, new_state, info = amp_opt.apply_gradients(
            grads, amp_state, params)
        # pipeline mode: reuse the norm sweep's measurement (see
        # standalone_gpt.train_smoke)
        gnorm = info.grad_norm if info.grad_norm is not None else \
            param_l2_norm(grads) / amp_state.scaler.loss_scale
        return new_params, new_state, loss, gnorm, info

    return _step


def build_train_step(setup: BertSmokeSetup, *, telemetry=None):
    """The jitted BERT smoke train step (LM + NSP loss through amp).
    ``params``/``amp_state`` are donated, exactly as in
    :func:`.standalone_gpt.build_train_step` — the loop rebinds both,
    and undonated masters/optimizer state double their HBM (APX601).
    ``telemetry`` (a ``DeviceMetricsBuffer``) switches to the deferred
    three-argument form, same as the GPT driver."""
    _step = make_step_fn(setup)
    if telemetry is None:
        return functools.partial(jax.jit, donate_argnums=(0, 1))(_step)
    from .standalone_gpt import wrap_deferred_step

    return wrap_deferred_step(_step, telemetry)


def build_train_step_scan(setup: BertSmokeSetup, k: int, *,
                          telemetry=None):
    """K BERT train steps per jit call — the batched-step scan driver,
    through the SAME :func:`.standalone_gpt.wrap_scan_step` the GPT
    driver uses (carry/donation/telemetry contract documented there)."""
    from .standalone_gpt import wrap_scan_step

    return wrap_scan_step(make_step_fn(setup), k, telemetry=telemetry)


def train_smoke(steps: int = 8, *, jsonl: Optional[str] = None,
                sink=None, vocab: int = 64, hidden: int = 32,
                num_heads: int = 4, num_layers: int = 2, batch: int = 4,
                seq: int = 16, opt_level: str = "O2", lr: float = 1e-3,
                stall_timeout: float = 300.0, seed: int = 0,
                ckpt_dir: Optional[str] = None, ckpt_every: int = 1,
                ckpt_keep: int = 3, resume: bool = True,
                fault=None, autoresume="auto", escalation=None,
                return_state: bool = False,
                trace_dir: Optional[str] = None,
                drain_every: Optional[int] = None,
                scan_steps: Optional[int] = None):
    """Tiny single-device BERT train loop wired through
    :mod:`apex_tpu.monitor` — the BERT sibling of
    :func:`apex_tpu.testing.standalone_gpt.train_smoke` (same event
    stream: step metrics, amp scale, phase timers, watchdog — and the
    same resilience wiring: periodic checkpoints + auto-resume under
    ``ckpt_dir``, deterministic ``fault`` injection, SIGTERM-safe
    exit; same observability wiring: ``trace_dir`` wall-time
    waterfall + Chrome export, ``drain_every`` deferred telemetry),
    proving both paths are driver-agnostic (``scan_steps`` >= 1: the
    batched-step scan driver, K steps per jit call — see the GPT
    docstring).  Returns the final loss, or
    ``(loss, params, amp_state, steps_done)`` with
    ``return_state=True``."""
    from ..transformer.pipeline_parallel.utils import Timers
    from ..utils.compile_cache import configure_compile_cache
    from .standalone_gpt import (_run_smoke_loop, make_smoke_monitor,
                                 resolve_driver_mode)

    configure_compile_cache()
    setup = make_smoke_setup(
        vocab=vocab, hidden=hidden, num_heads=num_heads,
        num_layers=num_layers, batch=batch, seq=seq,
        opt_level=opt_level, lr=lr, seed=seed)
    scan_steps, telemetry, step, scan_factory = resolve_driver_mode(
        setup, scan_steps, drain_every,
        build_step=build_train_step,
        build_step_scan=build_train_step_scan)
    params, amp_opt, amp_state = (setup.params, setup.amp_opt,
                                  setup.amp_state)
    n_params = setup.n_params
    monitor = make_smoke_monitor(
        jsonl, sink, tokens_per_step=batch * seq,
        flops_per_step=6.0 * n_params * batch * seq,
        stall_timeout=stall_timeout, escalation=escalation,
        run_attrs={"driver": "standalone_bert.train_smoke",
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
        trace=trace, telemetry=telemetry,
        scan_steps=scan_steps or 0, scan_factory=scan_factory)


def _main(argv=None):
    import argparse

    from .standalone_gpt import add_resilience_cli

    p = argparse.ArgumentParser(
        description="Monitored BERT smoke train loop (CPU-friendly); "
                    "writes an apex_tpu.monitor JSONL event log; "
                    "preemption-safe with --ckpt-dir.")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--jsonl", default=None,
                   help="event-log path (default: in-memory only)")
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--stall-timeout", type=float, default=300.0)
    p.add_argument("--trace", default=None, metavar="DIR",
                   help="wall-time attribution (see standalone_gpt)")
    p.add_argument("--telemetry-drain-every", type=int, default=None,
                   metavar="K", help="deferred telemetry cadence "
                                     "(see standalone_gpt)")
    p.add_argument("--scan-steps", type=int, default=None, metavar="K",
                   help="batched-step scan driver: K steps per jit "
                        "call (see standalone_gpt)")
    add_resilience_cli(p)
    args = p.parse_args(argv)
    loss, _, _, done = train_smoke(
        steps=args.steps, jsonl=args.jsonl, opt_level=args.opt_level,
        stall_timeout=args.stall_timeout, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, resume=not args.no_resume,
        fault=args.fault, return_state=True, trace_dir=args.trace,
        drain_every=args.telemetry_drain_every,
        scan_steps=args.scan_steps)
    print(f"SMOKE_DONE steps_done={done}"
          + (f" loss={loss:.4f}" if loss is not None else "")
          + (f" jsonl={args.jsonl}" if args.jsonl else ""))


if __name__ == "__main__":
    _main()


def bert_model_provider(args, pre_process=True, post_process=True,
                        **overrides):
    """ref: standalone_bert.py:215-223 — build from Megatron args."""
    del pre_process, post_process  # single-program model
    kw = dict(
        vocab_size=args.vocab_size,
        hidden_size=args.hidden_size,
        num_layers=args.num_layers,
        num_attention_heads=args.num_attention_heads,
        max_sequence_length=args.max_position_embeddings,
        attention_dropout=args.attention_dropout,
        hidden_dropout=args.hidden_dropout,
        checkpoint_activations=getattr(args, "checkpoint_activations",
                                       False),
        dtype=args.params_dtype,
    )
    kw.update(overrides)
    return BertModel(**kw)
