"""apex_tpu.serving — flash-decode inference stack.

The serving counterpart of the training pipeline (ROADMAP item 1):

* :mod:`.kv_cache` — block-paged KV cache: device layout
  (:class:`PagedKVCache`), host block pool
  (:class:`KVCacheManager`), bf16/int8 storage.
* :mod:`.model` — pure-function GPT prefill + paged decode over the
  extracted :class:`GPTServingWeights`.
  Three families of model (``ServingModelConfig.family``): GPT-2's
  block, :mod:`.rope_moe` (RMSNorm, rotary, grouped heads, windows, a
  dropless mixture of experts) and :mod:`.mla_moe` (latent attention
  over a paged latent cache, sandwich norms, a held share of the
  experts, the model's own MTP module as the engine's draft).
* :mod:`.engine` — continuous batching: bucket-laddered jitted steps,
  reservation admission, SIGTERM clean drain, tokens/s + p50/p99
  metrics (:class:`ServingEngine`), plus the decode fast path
  (ISSUE-12): copy-on-write prompt-prefix sharing, speculative
  decoding (draft-propose / multi-token verify, greedy-match
  acceptance — token-identical to plain greedy), and chunked
  prefill interleaved with decode ticks.
* :mod:`.metrics` — per-request lifecycle telemetry (queue wait /
  TTFT / ITL distributions, Perfetto request lanes), per-tick engine
  gauges (``serve_tick``), the on-demand engine snapshot
  (:class:`ServeMetrics`, :class:`EngineGauges`,
  :class:`SnapshotTrigger`), and per-priority-class SLOs with
  multi-window burn-rate alerting (ISSUE-17: :class:`SLObjective`,
  :class:`SLOTracker` — ``slo_burn`` episodes through the watchdog
  alarm machinery, surfaced in ``/healthz`` and ``SERVE_DONE``).
* :mod:`.resilience` — serving fault-tolerance (ISSUE-13): request
  deadlines + hysteresis load shedding (:class:`ShedPolicy`), the
  crash-safe :class:`RequestJournal` with supervised
  restart-and-replay (:func:`run_serving`, the PR-3 bounded-backoff
  semantics around one engine), and degraded modes
  (:class:`SpeculationGovernor` auto-disabling a mismatching draft,
  watchdog stall → snapshot-then-drain).

* :mod:`.control_plane` — process-isolated fleet (ISSUE-18): each
  replica is a supervised subprocess pinned to its device, speaking
  a length-prefixed JSON+binary protocol over local sockets for
  submits, gauge polls and KV handoff; heartbeat liveness (missed
  polls ⇒ SIGKILL + bounded-backoff restart with journal replay,
  fleet digest token-identical to an uninterrupted run), autoscaling
  from queue-depth trends and per-class QoS admission
  (:class:`ProcessFleet`, :class:`ReplicaProcess`,
  :class:`AutoscalePolicy`, :class:`QoSPolicy`).

Entry point: ``python -m apex_tpu.testing.standalone_gpt --serve``;
docs/api/serving.md walks the architecture.
"""
from .control_plane import (AutoscalePolicy, EngineSpec, FleetGiveUp,
                            ProcessFleet, ProcessFleetSummary,
                            QoSClass, QoSPolicy, ReplicaDead,
                            ReplicaProcess, RpcError, RpcRemoteError,
                            RpcTimeout, fleet_rows_digest, recv_frame,
                            send_frame)
from .engine import (BucketLadder, Request, ServeSummary,
                     ServingEngine, default_cache_config)
from .fleet import FleetRouter, FleetSummary, Replica, transfer_prefix
from .kv_cache import (DUMP_BLOCK, CachePoolExhausted, KVCacheConfig,
                       KVCacheManager, PagedKVCache, PageWrite,
                       PrefixMatch, init_cache, plan_page_write,
                       prefix_chain_keys, quantize_kv_rows,
                       write_prefill_kv, write_token_kv)
from .metrics import (EngineGauges, ReplicaMonitor, RequestTrace,
                      ServeMetrics, SLObjective, SLOTracker,
                      SnapshotTrigger)
from .ep import (SERVING_EP_AXIS, EPContext, expand_moe_weights,
                 serving_ep_plan)
from .model import (MOE_TICK_COUNTERS, GPTServingWeights, LayerSpec,
                    LayerWeights, MlaSpec, MoELayerWeights,
                    QuantGPTServingWeights, QuantLayerWeights,
                    RopeMoEWeights, RopeSpec, ServingModelConfig,
                    copy_cache_block, init_mla_moe_weights,
                    init_rope_moe_weights,
                    extract_serving_weights, gather_cache_blocks,
                    gpt_decode_step, gpt_extend_step,
                    gpt_prefill_step, gpt_sequence_logits,
                    quantize_weights, scatter_cache_blocks,
                    weights_in_compute_dtype)
from .resilience import (RequestJournal, ServeRunResult, ShedPolicy,
                         SpeculationGovernor, recover_engine,
                         run_serving)
from .tp import SERVING_TP_AXIS, TPContext, serving_tp_plan

__all__ = [
    "AutoscalePolicy", "EngineSpec", "FleetGiveUp", "ProcessFleet",
    "ProcessFleetSummary", "QoSClass", "QoSPolicy", "ReplicaDead",
    "ReplicaProcess", "RpcError", "RpcRemoteError", "RpcTimeout",
    "fleet_rows_digest", "recv_frame", "send_frame",
    "BucketLadder", "Request", "ServeSummary", "ServingEngine",
    "default_cache_config",
    "FleetRouter", "FleetSummary", "Replica", "transfer_prefix",
    "DUMP_BLOCK", "CachePoolExhausted", "KVCacheConfig",
    "KVCacheManager", "PagedKVCache", "PageWrite", "PrefixMatch",
    "init_cache", "plan_page_write", "prefix_chain_keys",
    "quantize_kv_rows", "write_prefill_kv", "write_token_kv",
    "GPTServingWeights", "LayerWeights", "MoELayerWeights",
    "QuantGPTServingWeights", "QuantLayerWeights",
    "ServingModelConfig",
    "RopeMoEWeights", "LayerSpec", "RopeSpec", "init_rope_moe_weights",
    "MlaSpec", "init_mla_moe_weights",
    "MOE_TICK_COUNTERS",
    "copy_cache_block", "extract_serving_weights",
    "gather_cache_blocks", "gpt_decode_step", "gpt_extend_step",
    "gpt_prefill_step", "gpt_sequence_logits", "quantize_weights",
    "scatter_cache_blocks", "weights_in_compute_dtype",
    "EngineGauges", "ReplicaMonitor", "RequestTrace", "ServeMetrics",
    "SLObjective", "SLOTracker", "SnapshotTrigger",
    "RequestJournal", "ServeRunResult", "ShedPolicy",
    "SpeculationGovernor", "recover_engine", "run_serving",
    "SERVING_TP_AXIS", "TPContext", "serving_tp_plan",
    "SERVING_EP_AXIS", "EPContext", "expand_moe_weights",
    "serving_ep_plan",
]
