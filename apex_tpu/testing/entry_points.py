"""Registry of the framework's lowerable entry points.

ONE list of real jitted steps, shared by everything that needs "the
programs this framework actually compiles":

* the compiled-graph auditor (:mod:`apex_tpu.analysis.hlo`) lowers each
  entry and checks donation, dtype promotion, the collective census,
  host transfers, and peak live memory against the committed baseline
  (``python -m apex_tpu.analysis --check-hlo``, tools/ci.sh step 7);
* the sanitizer smoke drives the GPT entry's exact step function;
* the train-smoke drivers build their steps through the same
  ``make_smoke_setup``/``build_train_step`` pair the entries here use.

Before this registry the smoke drivers, the sanitizer, and CI each
reconstructed their own copy of "the GPT step" — an audit of one said
nothing about the others.  Now an entry point is data: name, builder,
precision-policy tag, which arguments die at the call boundary
(donation candidates, APX601), which provenance paths are sanctioned
fp32 regions under the policy (APX602), and how many devices the build
needs (multichip entries lower on an 8-device host-platform mesh —
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the tests'
standing configuration).

Builders are lazy (nothing lowers at import) and cheap: tiny shapes,
CPU-lowerable, no compile — the auditor only needs ``.lower()``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = ["EntryPoint", "ENTRY_POINTS", "register_entry_point",
           "available_entry_points"]

# Provenance path substrings (repo-relative) where an fp32 upcast is
# the precision policy's own doing, shared by every low-precision
# entry: fp32 layer-norm statistics, fp32 softmax, fp32 loss, and the
# amp/optimizer machinery (masters, unscale, norm sweeps) are what
# O4/O5 *mean* — APX602 exists for upcasts outside this list.
POLICY_FP32_REGIONS = (
    "apex_tpu/normalization/",
    "apex_tpu/ops/layer_norm.py",
    "apex_tpu/ops/scaled_softmax.py",
    "apex_tpu/ops/flash_attention.py",
    "apex_tpu/contrib/xentropy/",
    "apex_tpu/transformer/tensor_parallel/cross_entropy.py",
    "apex_tpu/transformer/functional/fused_softmax.py",
    "apex_tpu/amp/",
    "apex_tpu/optimizers/",
    "apex_tpu/ops/fused_pipeline.py",
    "apex_tpu/ops/fused_optim.py",
    "apex_tpu/ops/multi_tensor.py",
    # the smoke drivers' own loss-side fp32 entry (gpt_loss /
    # bert lm+nsp mean): loss math is fp32 under every policy
    "apex_tpu/testing/standalone_gpt.py",
    "apex_tpu/testing/standalone_bert.py",
    # param_l2_norm / loss averaging: fp32 norm accumulation is the
    # same sanctioned class as multi_tensor.sumsq
    "apex_tpu/transformer/pipeline_parallel/utils.py",
    # serving: fp32 softmax/layer-norm statistics and int8 KV dequant
    # scales are the decode path's sanctioned fp32 regions
    "apex_tpu/serving/",
    "apex_tpu/ops/flash_decode.py",
    # Q8: fp32 accumulation is the quantized matmul's contract (the
    # activation upcast feeding the int8 contraction) — APX606, not
    # APX602, polices what may leave this module
    "apex_tpu/ops/quant_matmul.py",
)


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    """One lowerable entry point: the registry row the auditor walks.

    ``build()`` returns ``(fn, args)`` with ``fn`` a ``jax.jit``-wrapped
    callable and ``args`` example arguments — the auditor calls
    ``fn.lower(*args)`` and never executes the step.
    """

    name: str
    build: Callable[[], Tuple[Any, tuple]]
    # O-level tag; 'O4'/'O5' arms APX602 (silent bf16/f16->f32
    # promotion) for this entry.
    policy: Optional[str] = None
    # Positional argnums whose buffers are dead after the call (the
    # caller rebinds them) — donation candidates for APX601.
    dead_args: Tuple[int, ...] = ()
    # Extra sanctioned-fp32 provenance substrings on top of
    # POLICY_FP32_REGIONS.
    allow_upcast: Tuple[str, ...] = ()
    min_devices: int = 1
    doc: str = ""
    # Lazy MeshPlan constructor: the entry's declared topology contract
    # (axes + kinds, per-tensor partition specs, collective budget).
    # Entries that carry one are compiled under their mesh by the SPMD
    # auditor (apex_tpu.analysis.sharding, APX701-705) and their plan
    # is committed to tools/sharding_baseline.json — a topology change
    # is a reviewed JSON diff.  The builder itself must derive its
    # runtime in/out specs from the SAME plan, or the auditor will
    # report the drift.
    plan: Optional[Callable[[], Any]] = None


ENTRY_POINTS: Dict[str, EntryPoint] = {}


def register_entry_point(name: str, build, **kw) -> EntryPoint:
    if name in ENTRY_POINTS:
        raise ValueError(f"duplicate entry point registration: {name}")
    ep = EntryPoint(name=name, build=build, **kw)
    ENTRY_POINTS[name] = ep
    return ep


def available_entry_points() -> Dict[str, EntryPoint]:
    """Entries buildable on this host (device-count gate)."""
    import jax

    n = jax.device_count()
    return {k: v for k, v in ENTRY_POINTS.items() if v.min_devices <= n}


# ---------------------------------------------------------------------------
# Single-chip entries: the smoke train steps and the fused pipeline
# ---------------------------------------------------------------------------

def _build_gpt_train_step():
    from .standalone_gpt import build_train_step, make_smoke_setup

    setup = make_smoke_setup(opt_level="O2")
    return build_train_step(setup), (setup.params, setup.amp_state)


def _build_gpt_train_step_o5():
    import jax.numpy as jnp

    from .standalone_gpt import build_train_step, make_smoke_setup

    setup = make_smoke_setup(opt_level="O5", dtype=jnp.bfloat16)
    return build_train_step(setup), (setup.params, setup.amp_state)


def _build_bert_train_step():
    from .standalone_bert import build_train_step, make_smoke_setup

    setup = make_smoke_setup(opt_level="O2")
    return build_train_step(setup), (setup.params, setup.amp_state)


def _build_gpt_train_step_deferred():
    """The deferred-telemetry smoke step: the GPT train step with the
    per-step scalars (loss / grad-norm / scale state) appended into a
    device-resident :class:`apex_tpu.monitor.tracing.
    DeviceMetricsBuffer` ring INSIDE the jit.  Auditing it proves
    statically what the runtime sanitizer proves dynamically: the
    deferred mode compiles in zero host transfers (APX604) and the
    ring state donates cleanly alongside params/amp state (APX601) —
    observability is no longer part of the host time it measures."""
    from ..monitor.tracing import DeviceMetricsBuffer
    from .standalone_gpt import build_train_step, make_smoke_setup

    setup = make_smoke_setup(opt_level="O2")
    buf = DeviceMetricsBuffer(capacity=4)
    return (build_train_step(setup, telemetry=buf),
            (setup.params, setup.amp_state, buf.init()))


def _build_gpt_train_step_scan():
    """The ISSUE-8 batched-step scan driver: K=4 GPT train steps per
    jit call (``build_train_step_scan``) with the deferred-telemetry
    ring appended inside the scan body.  Auditing it proves the whole
    hot path stays clean when K steps fuse into one dispatch: params,
    amp state (masters + packed m/v + scaler), and the ring all donate
    through the scan carry (APX601 — a missed donation here costs
    K-fold nothing extra, but it doubles the largest buffers exactly
    like the per-step entry), and zero host transfers compile in
    (APX604).  The census walker multiplies scan-body ops by the trip
    count, so any per-step collective would be priced K times."""
    from ..monitor.tracing import DeviceMetricsBuffer
    from .standalone_gpt import build_train_step_scan, make_smoke_setup

    setup = make_smoke_setup(opt_level="O2")
    buf = DeviceMetricsBuffer(capacity=4)
    return (build_train_step_scan(setup, 4, telemetry=buf),
            (setup.params, setup.amp_state, buf.init()))


def _build_gpt_decode_step():
    """The serving stack's hot path (ISSUE-9): one bucketed
    continuous-batching decode step — embed one token per sequence,
    per layer write its k/v into the block-paged cache then attend
    over the pages through the Pallas flash-decode kernel, greedy-
    sample in-graph.  Auditing it proves the per-token serving cost
    statically: the paged cache (the largest serving buffer — double-
    buffering it halves capacity) donates through every step (APX601),
    and zero host transfers compile in (APX604) — the engine's only
    per-tick fetch is the explicit (b,) next-token readout.  Built at
    the bf16 O5 surface so APX602 guards the decode path's precision
    regime exactly as it guards training."""
    import jax.numpy as jnp

    from ..serving import (BucketLadder, ServingEngine,
                           ServingModelConfig, default_cache_config,
                           extract_serving_weights)
    from .standalone_gpt import make_smoke_setup

    setup = make_smoke_setup(opt_level="O5", dtype=jnp.bfloat16)
    cfg = ServingModelConfig.from_model(setup.model)
    weights = extract_serving_weights(setup.params, cfg.num_layers)
    cache_cfg = default_cache_config(cfg, num_blocks=8, block_size=4)
    engine = ServingEngine(weights, cfg, cache_cfg,
                           ladder=BucketLadder(batch=(2,), pages=(2,)))
    return engine._jit_decode(), engine._decode_args(2, 2)


def _build_gpt_decode_step_q8():
    """The ISSUE-16 Q8 serving tier: the SAME continuous-batching
    decode step as ``gpt_decode_step`` with the weight pytree
    quantized to per-output-channel int8
    (:func:`apex_tpu.ops.quant_matmul.quantize_weights`).  Built at
    the Q8 policy surface so the compiled-graph audit holds the
    quantized hot path to BOTH precision contracts: APX602 (no
    unsanctioned bf16→f32 activation upcasts, same as O5) and APX606
    (no weight-sized int8→float convert outside the quant kernel
    family — the dequant must stay tile-local, never an HLO-visible
    fp32 weight resident).  Donation and host-transfer guarantees are
    unchanged from the bf16 entry."""
    import jax.numpy as jnp

    from ..ops.quant_matmul import quantize_weights
    from ..serving import (BucketLadder, ServingEngine,
                           ServingModelConfig, default_cache_config,
                           extract_serving_weights)
    from .standalone_gpt import make_smoke_setup

    setup = make_smoke_setup(opt_level="O5", dtype=jnp.bfloat16)
    cfg = ServingModelConfig.from_model(setup.model)
    weights = quantize_weights(
        extract_serving_weights(setup.params, cfg.num_layers))
    cache_cfg = default_cache_config(cfg, num_blocks=8, block_size=4)
    engine = ServingEngine(weights, cfg, cache_cfg,
                           ladder=BucketLadder(batch=(2,), pages=(2,)))
    return engine._jit_decode(), engine._decode_args(2, 2)


def _build_gpt_decode_step_tp():
    """The ISSUE-14 tensor-parallel serving decode step: the SAME
    continuous-batching decode program as ``gpt_decode_step``, shard-
    mapped over a 2-way MeshPlan ``tensor`` axis — heads and ffn
    columns local, the paged KV cache sharded on its head axis, 2
    psums per layer (attention dense + MLP fc2, the Megatron
    forward).  The plan is the runtime's own
    :func:`apex_tpu.serving.tp.serving_tp_plan`, so the SPMD auditor
    (APX701/703/705) guards the serving topology exactly as it
    guards training: a replicated cache shard or an extra all-reduce
    is a CI failure here before it is a TPU bill.  APX601 proves the
    sharded cache still donates end to end; APX604 that zero host
    transfers compile in — the engine's one fetch per tick stays the
    explicit (b,) next-token readout."""
    import jax.numpy as jnp

    from ..serving import (BucketLadder, ServingEngine,
                           ServingModelConfig, TPContext,
                           default_cache_config,
                           extract_serving_weights)
    from .standalone_gpt import make_smoke_setup

    setup = make_smoke_setup(opt_level="O5", dtype=jnp.bfloat16)
    cfg = ServingModelConfig.from_model(setup.model)
    weights = extract_serving_weights(setup.params, cfg.num_layers)
    cache_cfg = default_cache_config(cfg, num_blocks=8, block_size=4)
    tp = TPContext(cfg, cache_cfg, 2)
    engine = ServingEngine(weights, cfg, cache_cfg,
                           ladder=BucketLadder(batch=(2,), pages=(2,)),
                           tp=tp)
    return engine._jit_decode(), engine._decode_args(2, 2)


def _serving_tp_plan():
    """gpt_decode_step_tp's contract = the serving stack's own
    :func:`~apex_tpu.serving.tp.serving_tp_plan` (tp=2 over the
    2-layer smoke GPT, bf16 cache): qkv/fc1 column-split, dense/fc2
    row-split, cache head-axis sharded in AND out, 2 psums per
    layer."""
    from ..serving.tp import serving_tp_plan

    return serving_tp_plan(2, num_layers=2, quantized=False)


def _build_gpt_decode_step_ep():
    """The ISSUE-19 expert-parallel serving decode step: the smoke
    GPT's MLPs expanded to a 4-expert Switch MoE
    (:func:`~apex_tpu.serving.ep.expand_moe_weights`) and the
    continuous-batching decode program shard-mapped over a 2-way
    MeshPlan ``expert`` axis — expert stacks split, attention and the
    paged cache replicated.  Per MoE layer the trace carries the
    fused routing front (:func:`~apex_tpu.ops.moe_routing.
    moe_route_dispatch`), the capacity-chunked OVERLAPPED all_to_all
    exchange (``moe_a2a_chunks=2`` — the schedule APX704 certifies
    quiet on the training entry), and one masked psum replicating the
    combined token slice.  ``moe_capacity_factor=8.0`` keeps the
    per-rank capacity ≥ chunks at the 2-token decode bucket so the
    chunked exchange actually engages.  The plan is the runtime's own
    :func:`~apex_tpu.serving.ep.serving_ep_plan`, so APX701/703/705
    guard the MoE serving topology like training; APX601 proves the
    replicated cache still donates end to end, APX604 that the
    engine's one fetch per tick stays the only host transfer."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ..serving import (BucketLadder, EPContext, ServingEngine,
                           ServingModelConfig, default_cache_config,
                           expand_moe_weights, extract_serving_weights)
    from .standalone_gpt import make_smoke_setup

    setup = make_smoke_setup(opt_level="O5", dtype=jnp.bfloat16)
    cfg = ServingModelConfig.from_model(setup.model)
    cfg = dataclasses.replace(cfg, num_experts=4,
                              moe_capacity_factor=8.0, moe_a2a_chunks=2)
    weights = expand_moe_weights(
        extract_serving_weights(setup.params, cfg.num_layers), 4,
        jax.random.PRNGKey(0))
    cache_cfg = default_cache_config(cfg, num_blocks=8, block_size=4)
    ep = EPContext(cfg, cache_cfg, 2)
    engine = ServingEngine(weights, cfg, cache_cfg,
                           ladder=BucketLadder(batch=(2,), pages=(2,)),
                           ep=ep)
    return engine._jit_decode(), engine._decode_args(2, 2)


def _serving_ep_plan():
    """gpt_decode_step_ep's contract = the serving stack's own
    :func:`~apex_tpu.serving.ep.serving_ep_plan` (ep=2 over the
    2-layer 4-expert smoke MoE GPT): wi/wo expert-sharded, everything
    else replicated, 2·chunks all_to_all + 1 psum per layer."""
    from ..serving.ep import serving_ep_plan

    return serving_ep_plan(2, num_layers=2, a2a_chunks=2)


def _build_fused_pipeline_step():
    """The PR-4 persistent packed optimizer pipeline as its own entry:
    one full amp post-backward step (pack -> norm/finite sweep ->
    clip/update/cast sweep) with ``pipeline=True`` forced, grads/state/
    model donated — masters and optimizer state live in the packed
    buffers, so a missed donation here doubles the largest allocations
    in the whole step (the APX601 end-to-end requirement)."""
    import functools

    import jax
    import jax.numpy as jnp

    from .. import amp
    from ..optimizers import fused_adam

    params = {
        "w": jnp.linspace(-1.0, 1.0, 4096,
                          dtype=jnp.float32).reshape(32, 128),
        "b": jnp.linspace(0.1, 0.5, 128, dtype=jnp.float32),
        "deep": {"k": jnp.full((16, 128), 0.25, jnp.float32)},
    }
    amp_opt = amp.AmpOptimizer(
        fused_adam(1e-3, weight_decay=0.01, max_grad_norm=1.0),
        amp.get_policy("O5", loss_scale=1024.0), check_finite=True,
        pipeline=True)
    amp_state = amp_opt.init(params)
    model = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), params)
    grads = jax.tree_util.tree_map(
        lambda x: (x * 0.001 * 1024.0).astype(jnp.bfloat16), params)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def post_backward_step(grads, amp_state, model):
        new_model, new_state, info = amp_opt.apply_gradients(
            grads, amp_state, model)
        return new_model, new_state, info.grad_norm

    return post_backward_step, (grads, amp_state, model)


def _build_flash_attention_grad():
    """The flash-attention call site, fwd+bwd: whatever branch is
    legal on this backend (Pallas kernels on TPU, the dispatching
    fallback elsewhere) is exactly what the auditor should see —
    auditing a forced branch would certify a graph production never
    runs."""
    import jax
    import jax.numpy as jnp

    from ..ops.flash_attention import flash_attention

    b, h, s, d = 2, 4, 128, 64
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (b, h, s, d), jnp.bfloat16)
               for i in range(3))

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))), (q, k, v)


register_entry_point(
    "gpt_train_step", _build_gpt_train_step, policy="O2",
    dead_args=(0, 1),
    doc="standalone-GPT smoke train step (O2 fp16, dynamic scaling) — "
        "the step the sanitizer smoke and CI telemetry smoke drive")
register_entry_point(
    "gpt_train_step_o5", _build_gpt_train_step_o5, policy="O5",
    dead_args=(0, 1),
    doc="standalone-GPT train step under the O5 bf16 policy — the "
        "APX602 promotion-audit surface")
register_entry_point(
    "bert_train_step", _build_bert_train_step, policy="O2",
    dead_args=(0, 1),
    doc="standalone-BERT smoke train step (LM + NSP loss)")
register_entry_point(
    "gpt_train_step_deferred", _build_gpt_train_step_deferred,
    policy="O2", dead_args=(0, 1, 2),
    doc="GPT smoke train step with the deferred-telemetry device ring "
        "appended in-jit (monitor.tracing.DeviceMetricsBuffer) — the "
        "static zero-host-transfer proof; params/state/ring donated")
register_entry_point(
    "gpt_train_step_scan", _build_gpt_train_step_scan,
    policy="O2", dead_args=(0, 1, 2),
    doc="K=4 batched-step scan driver (lax.scan over the GPT smoke "
        "train step, telemetry ring appended in-body) — params/amp "
        "state/ring donated through the scan carry; the "
        "dispatch-amortized hot path the smoke drivers run under "
        "--scan-steps / APEX_TPU_SCAN_STEPS")
register_entry_point(
    "gpt_decode_step", _build_gpt_decode_step, policy="O5",
    dead_args=(1,),
    doc="serving-stack continuous-batching decode step (paged KV "
        "write + flash-decode attention + in-graph greedy sampling, "
        "one (batch=2, pages=2) bucket) — the cache carry donated, "
        "zero compiled-in host transfers; what standalone_gpt "
        "--serve runs per tick")
register_entry_point(
    "gpt_decode_step_q8", _build_gpt_decode_step_q8, policy="Q8",
    dead_args=(1,),
    doc="Q8 serving decode step: int8 weight-only matmuls "
        "(ops/quant_matmul) on the same bucketed decode program — "
        "the APX606 dequant-residency audit surface (what "
        "standalone_gpt --serve --policy Q8 runs per tick)")
register_entry_point(
    "fused_pipeline_step", _build_fused_pipeline_step, policy="O5",
    dead_args=(0, 1, 2),
    doc="persistent packed optimizer pipeline post-backward step "
        "(pipeline=True forced), grads/state/model donated")
register_entry_point(
    "flash_attention_grad", _build_flash_attention_grad, policy="O5",
    dead_args=(),
    # the builder's own loss sums in fp32 on purpose (loss math is
    # fp32 under every policy)
    allow_upcast=("apex_tpu/testing/entry_points.py",),
    doc="flash-attention fwd+bwd call site (q/k/v retained by the "
        "caller — no donation expected)")


# ---------------------------------------------------------------------------
# Multichip entries (8-device host-platform mesh): the collective
# census must cover the parallel stack, not just single-chip steps.
# Each carries a MeshPlan — the SPMD auditor compiles it under its mesh
# and checks the partitioner's output against the plan (APX701-705).
# ---------------------------------------------------------------------------

def plan_shardings(plan, mesh, args: tuple):
    """Per-leaf ``NamedSharding`` tree for ``args`` from the plan's
    declared specs, named exactly as the auditor names them (``in0``,
    ``in1['w']``, ``in2.m[0]``): the builder's ``in_shardings`` and the
    audit read the SAME contract, so a builder that stops consulting
    the plan becomes an APX701/703 finding, not a silent regression."""
    import jax
    from jax.sharding import NamedSharding

    def leaf(prefix):
        def f(path, _):
            name = prefix + jax.tree_util.keystr(path)
            return NamedSharding(mesh, plan.partition_spec(name))

        return f

    return tuple(
        jax.tree_util.tree_map_with_path(leaf(f"in{i}"), a)
        for i, a in enumerate(args))


def _build_dp8_train_step():
    """Pure data-parallel GPT loss step over an 8-way mesh: pmean of
    the loss inside shard_map, gradient psum from boundary
    transposition (replicated params sum their cotangents) — the
    collectives every DP run emits."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map
    from ..optimizers import fused_adam
    from .standalone_gpt import GPTModel, gpt_loss, unbox

    vocab, hidden, heads, layers, seq = 64, 32, 4, 2, 16
    batch = 16  # 2 per device
    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_sequence_length=seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(jax.random.fold_in(key, 1),
                                (batch, seq), 0, vocab)
    labels = jnp.roll(tokens, -1, -1)
    # raw arrays: a boxed ("tensor", None) leaf makes flax constrain it
    # to a `tensor` axis the data-only mesh inside shard_map lacks
    params = unbox(jax.jit(model.init)(key, tokens[:2])["params"])
    tx = fused_adam(1e-3)
    opt_state = jax.jit(tx.init)(params)
    plan = _dp8_plan()
    mesh = plan.make_mesh()

    def loss_fn(p, t, l):
        def shard(p, t, l):
            loss = gpt_loss(model.apply({"params": p}, t), l)
            return jax.lax.pmean(loss, "data")

        return shard_map(shard, mesh=mesh,
                         in_specs=(P(), P("data"), P("data")),
                         out_specs=P(), check_vma=False)(p, t, l)

    args = (params, opt_state, tokens, labels)

    @functools.partial(
        jax.jit, donate_argnums=(0, 1),
        in_shardings=plan_shardings(plan, mesh, args))
    def train_step(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens,
                                                  labels)
        updates, new_opt = tx.update(grads, opt_state, params)
        import optax

        return optax.apply_updates(params, updates), new_opt, loss

    return train_step, args


def _build_zero_dp8_update_step():
    """ZeRO-style sharded update over 8 devices: grads psum_scatter'd
    (each device reduces+keeps 1/8th), the shard updated locally, the
    updated shard all_gather'd back into replicated params — the
    reduce_scatter + all_gather pair the census must price."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map

    n = 8
    dim = 1024  # divisible by 8
    key = jax.random.PRNGKey(0)
    params = jax.random.normal(key, (dim, 64), jnp.float32)
    grads = params * 1e-3
    plan = _zero_update_plan()
    mesh = plan.make_mesh()

    def update(p, g):
        def shard(p, g):
            # g arrives FULL (replicated, as from a DP backward);
            # reduce+shard it, step the local shard, regather.
            g_shard = jax.lax.psum_scatter(g, "zero",
                                           scatter_dimension=0,
                                           tiled=True)
            i = jax.lax.axis_index("zero")
            rows = p.shape[0] // n
            p_shard = jax.lax.dynamic_slice_in_dim(p, i * rows, rows, 0)
            p_new = p_shard - 0.1 * g_shard
            return jax.lax.all_gather(p_new, "zero", axis=0,
                                      tiled=True)

        return shard_map(shard, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)(p, g)

    args = (params, grads)
    return (functools.partial(
        jax.jit, donate_argnums=(0,),
        in_shardings=plan_shardings(plan, mesh, args))(update), args)


def _build_zero_dp8_adam_step():
    """The REAL ZeRO optimizer over 8 devices with its persistent
    state crossing the jit boundary: DistributedFusedAdam's m/v flat
    buffers live sharded 1/8 over the ``zero`` axis (the memory saving
    that IS ZeRO), enter and leave the step as ``P('zero')`` globals,
    and the in/out specs derive from :func:`zero_adam_plan` — the same
    object the SPMD auditor checks.  A builder change that stops
    consulting the plan (the driver bug the auditor first caught
    carried the state as ``P()``) makes the state replicated and fires
    APX701 here instead of surfacing as a TPU bill."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map
    from ..contrib.optimizers import distributed_fused_adam

    plan = _zero_adam_entry_plan()
    mesh = plan.make_mesh()
    key = jax.random.PRNGKey(3)
    params = {"w": jax.random.normal(key, (512, 16), jnp.float32),
              "b": jnp.zeros((16,), jnp.float32)}
    grads = jax.tree_util.tree_map(lambda x: x * 1e-3 + 1e-4, params)
    tx = distributed_fused_adam(1e-2, axis_name="zero",
                                use_pallas=False)

    def state_specs(state):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: plan.partition_spec(
                "state" + jax.tree_util.keystr(path)), state)

    # init must run inside shard_map (shard sizes read the axis size);
    # learn the state's tree structure first (out_specs P() never
    # executes under eval_shape), then stitch the per-device shards
    # into P('zero') globals with the plan's real per-leaf specs
    shapes = jax.eval_shape(
        lambda p: shard_map(tx.init, mesh=mesh, in_specs=P(),
                            out_specs=P(), check_vma=False)(p),
        params)
    state = shard_map(tx.init, mesh=mesh, in_specs=P(),
                      out_specs=state_specs(shapes),
                      check_vma=False)(params)

    def step(params, state, grads):
        def shard(p, s, g):
            updates, s2 = tx.update(g, s, p)
            return optax.apply_updates(p, updates), s2

        return shard_map(
            shard, mesh=mesh,
            in_specs=(P(), state_specs(state), P()),
            out_specs=(P(), state_specs(state)),
            check_vma=False)(params, state, grads)

    args = (params, state, grads)
    return (functools.partial(
        jax.jit, donate_argnums=(0, 1),
        in_shardings=plan_shardings(plan, mesh, args))(step), args)


def _build_moe_ep8_train_step():
    """Top-2 (GShard) expert-parallel MoE train step over an 8-way
    ``expert`` mesh: the layer's OWN :meth:`ExpertParallelMLP.
    mesh_plan` supplies the axes, the wi/wo-sharded + router-replicated
    specs, and the all_to_all budget (2 hops per capacity chunk of the
    overlapped exchange forward, their transposes backward) the census
    is held to."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map
    from ..transformer.expert_parallel import ExpertParallelMLP

    n = 8
    layer = ExpertParallelMLP(hidden_size=16, ffn_hidden_size=32,
                              num_experts=n, capacity_factor=4.0,
                              router="top2")
    plan = _moe_ep8_plan()
    mesh = plan.make_mesh()
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (16 * n, 16),
                          jnp.float32) * 0.5

    def loss_fn(p, x):
        def f(p, x):
            y, aux = layer.apply(p, x)
            return jax.lax.psum(jnp.sum(y ** 2) + 0.01 * aux,
                                "expert")

        return shard_map(
            f, mesh=mesh,
            in_specs=({"router": P(), "wi": P("expert"),
                       "wo": P("expert")}, P("expert")),
            out_specs=P(), check_vma=False)(p, x)

    args = (params, x)
    return (functools.partial(
        jax.jit,
        in_shardings=plan_shardings(plan, mesh, args))(
            jax.value_and_grad(loss_fn)), args)


def _dp8_plan():
    """gpt_dp8_train_step's contract: one data axis, batch sharded,
    params/opt-state replicated (plain DP — ZeRO is the other entry),
    and the DP collective pair: ONE loss pmean + ONE fused gradient
    psum from the boundary transposition."""
    from ..mesh_plan import MeshPlan

    return MeshPlan.build(
        axes=(("data", 8, "data"),),
        tensor_specs={
            r"^in[23]$": ("data",),     # tokens / labels, batch dim
            r"^in[01]": (),             # params + adam state: replicated
        },
        # 1 loss pmean + one psum per replicated param leaf from the
        # boundary transposition (the UNFUSED per-leaf grad sync —
        # fusing it into one tree-psum is the budget cut ROADMAP item
        # 3 can bank, and this number is where it would show)
        collective_budget={"psum": 30})


def _zero_update_plan():
    """zero_dp8_update_step's contract: one zero-kind axis; params and
    grads replicated at the boundary (the entry models the update
    glue, not persistent state — zero_dp8_adam_step audits that); one
    reduce_scatter + one all_gather per step."""
    from ..mesh_plan import MeshPlan

    return MeshPlan.build(
        axes=(("zero", 8, "zero"),),
        tensor_specs={r"^in[01]$": (), r"^out0$": ()},
        collective_budget={"reduce_scatter": 1, "all_gather": 1})


def _zero_adam_entry_plan():
    """zero_dp8_adam_step's contract = the OPTIMIZER's own plan
    (:func:`~apex_tpu.contrib.optimizers.zero_adam_plan`: m/v sharded
    1/8 over the zero axis, count replicated, one reduce_scatter + one
    all_gather per dtype group) specialized with the entry's
    replicated params/grads boundary."""
    from ..contrib.optimizers import zero_adam_plan

    return zero_adam_plan(8, axis_name="zero").with_specs(
        {r"^in[02]": (), r"^out0$": ()})


def _moe_ep8_plan():
    """moe_ep8_train_step's contract = the LAYER's own
    :meth:`ExpertParallelMLP.mesh_plan` (wi/wo expert-sharded, router
    replicated, 2 all_to_all per capacity chunk with the backward —
    8 at the default ``APEX_TPU_MOE_A2A_CHUNKS=2``) specialized with
    the entry's token sharding and its loss/grad psum pair."""
    from ..transformer.expert_parallel import ExpertParallelMLP

    layer = ExpertParallelMLP(hidden_size=16, ffn_hidden_size=32,
                              num_experts=8, capacity_factor=4.0,
                              router="top2")
    # psum: the forward loss psum + its per-operand backward partials
    # as this jax transposes them (measured 3 on jax 0.9.0 with the
    # fused routing front)
    return layer.mesh_plan(8).with_specs(
        {r"^in1$": ("expert",)}, budget={"psum": 3})


register_entry_point(
    "gpt_dp8_train_step", _build_dp8_train_step, policy="O0",
    dead_args=(0, 1), min_devices=8, plan=_dp8_plan,
    doc="8-way data-parallel GPT train step (pmean loss, psum grad "
        "sync from boundary transposition)")
register_entry_point(
    "zero_dp8_update_step", _build_zero_dp8_update_step, policy="O0",
    dead_args=(0,), min_devices=8, plan=_zero_update_plan,
    doc="ZeRO-sharded update: psum_scatter grads -> local shard "
        "update -> all_gather params")
register_entry_point(
    "zero_dp8_adam_step", _build_zero_dp8_adam_step, policy="O0",
    dead_args=(0, 1), min_devices=8, plan=_zero_adam_entry_plan,
    doc="DistributedFusedAdam ZeRO step with the sharded m/v state "
        "crossing the jit boundary as P('zero') globals — specs "
        "derived from zero_adam_plan, the APX701 guard surface")
register_entry_point(
    "moe_ep8_train_step", _build_moe_ep8_train_step, policy="O0",
    dead_args=(), min_devices=8, plan=_moe_ep8_plan,
    doc="top-2 GShard MoE train step over expert=8 — the layer's own "
        "mesh_plan supplies specs and the all_to_all budget")
register_entry_point(
    "gpt_decode_step_tp", _build_gpt_decode_step_tp, policy="O5",
    dead_args=(1,), min_devices=2, plan=_serving_tp_plan,
    doc="tensor-parallel serving decode step (tp=2): head-sharded "
        "paged attention + column/row-split MLP under shard_map, "
        "2 psums per layer, cache donated through the sharded carry "
        "— the serving topology audited like training "
        "(what --serve-fleet --tp runs per tick)")
register_entry_point(
    "gpt_decode_step_ep", _build_gpt_decode_step_ep, policy="O5",
    dead_args=(1,), min_devices=2, plan=_serving_ep_plan,
    # the MoE combine accumulates gate-weighted expert outputs in
    # fp32 on purpose (router probabilities are fp32, and a bf16 sum
    # across chunks/experts would break the bit-exact single-buffer
    # equivalence the routing tests pin) — same sanctioned class as
    # the softmax/layer-norm statistics
    allow_upcast=("apex_tpu/transformer/expert_parallel.py",
                  "apex_tpu/ops/moe_routing.py"),
    doc="expert-parallel MoE serving decode step (ep=2, 4 experts): "
        "fused top-1 routing + capacity-chunked overlapped "
        "all_to_all exchange + one masked psum per layer under "
        "shard_map, expert stacks sharded and attention/cache "
        "replicated, cache donated through the carry — the ISSUE-19 "
        "MoE decode fast path audited like training "
        "(what --serve --ep runs per tick)")


# ---------------------------------------------------------------------------
# AOT warmup: pre-compile the registry (ISSUE-8 tentpole c)
# ---------------------------------------------------------------------------

def aot_warmup(names=None, *, configure_cache: bool = True):
    """``jit(...).lower().compile()`` every (buildable) registry entry
    point ahead of time — no execution, just the compile.  With the
    persistent compilation cache configured
    (``APEX_TPU_COMPILE_CACHE_DIR``; wired here unless
    ``configure_cache=False``), one warmup run per host populates the
    on-disk cache and every later process — smoke drivers, bench
    sections, tests — warm-starts its compiles from it, so cold-start
    and retrace cost stop polluting wall measurements.

    ``names`` restricts to specific entries (unknown names raise,
    naming the registry — a typo must not produce a do-nothing warmup
    that claims success); entries this host cannot build (device-count
    gate) are skipped and reported as None.  Returns
    ``{name: compile_ms | None}``.
    """
    import time

    from ..utils.compile_cache import configure_compile_cache

    if configure_cache:
        configure_compile_cache()
    if names is not None:
        unknown = sorted(set(names) - set(ENTRY_POINTS))
        if unknown:
            raise KeyError(
                f"unknown entry point(s) {unknown}; registered: "
                f"{sorted(ENTRY_POINTS)}")
    avail = available_entry_points()
    out = {}
    for name in sorted(names if names is not None else avail):
        ep = avail.get(name)
        if ep is None:
            out[name] = None  # device-count gated on this host
            continue
        fn, args = ep.build()
        t0 = time.perf_counter()
        fn.lower(*args).compile()
        out[name] = round((time.perf_counter() - t0) * 1e3, 1)
    return out


def _main(argv=None):
    """CLI: ``python -m apex_tpu.testing.entry_points --aot`` —
    pre-compile the registry into the persistent cache (tools/ci.sh
    step 9 proves the second process warm-starts from it)."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.testing.entry_points",
        description="Registry of lowerable entry points; --aot "
                    "pre-compiles them (persistent cache per "
                    "APEX_TPU_COMPILE_CACHE_DIR).")
    ap.add_argument("--aot", action="store_true",
                    help="lower+compile every buildable entry point")
    ap.add_argument("--entry", action="append", default=None,
                    help="restrict to this entry (repeatable)")
    ap.add_argument("--expect-cache-hits", action="store_true",
                    help="fail (exit 1) unless at least one compile "
                         "was served from the persistent cache — the "
                         "second-process warm-start proof")
    args = ap.parse_args(argv)
    if not args.aot:
        for name, ep in sorted(ENTRY_POINTS.items()):
            print(f"{name}: {ep.doc}")
        return 0
    hits = []
    if args.expect_cache_hits:
        # jax logs "Persistent compilation cache hit for '<name>'"
        # through jax._src.compiler when jax_log_compiles is on;
        # capturing it is the ground truth that the compile was read
        # from disk rather than redone.  The flag also makes the
        # dispatch/pxla loggers chatty — keep the capture out of the
        # console (the sanitizer's discipline): capture-only handler,
        # propagation off, NullHandlers so logging.lastResort stays
        # quiet.
        import logging
        import re

        import jax

        class _Hits(logging.Handler):
            def emit(self, record):
                m = re.search(r"Persistent compilation cache hit",
                              record.getMessage())
                if m:
                    hits.append(record.getMessage())

        lg = logging.getLogger("jax._src.compiler")
        lg.addHandler(_Hits())
        if lg.level > logging.DEBUG:
            lg.setLevel(logging.DEBUG)
        for name in ("jax._src.compiler", "jax._src.dispatch",
                     "jax._src.interpreters.pxla"):
            noisy = logging.getLogger(name)
            noisy.addHandler(logging.NullHandler())
            noisy.propagate = False
        jax.config.update("jax_log_compiles", True)
    res = aot_warmup(args.entry)
    for name, ms in res.items():
        state = "SKIPPED (device count)" if ms is None else f"{ms} ms"
        print(f"[aot] {name}: {state}")
    compiled = [ms for ms in res.values() if ms is not None]
    print(f"[aot] {len(compiled)} entry point(s) compiled, "
          f"{sum(compiled):.0f} ms total"
          + (f", {len(hits)} persistent-cache hit(s)"
             if args.expect_cache_hits else ""))
    if args.expect_cache_hits and not hits:
        print("[aot] FAIL: no persistent-cache hits — the warmup did "
              "not warm-start (is APEX_TPU_COMPILE_CACHE_DIR set and "
              "pre-populated?)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main())
