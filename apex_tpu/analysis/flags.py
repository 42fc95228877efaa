"""Central registry of every ``APEX_TPU_*`` environment flag.

One declaration per flag — name, type, default, constraints, doc — and
typed accessors that read the environment **per call** (setting a flag
after import still takes effect wherever the consuming module reads
per call) with hard errors on malformed values
(``APEX_TPU_STEP_PALLAS_MIN=abc`` names the flag, the raw value, and
what was expected).

Library code must not touch ``os.environ``/``os.getenv`` directly: the
trace-safety linter (rule APX301) fails on any env read outside this
module, and the flag table in docs/api/ops.md is generated from this
registry (``python -m apex_tpu.analysis --flag-table``), so docs cannot
drift from code.

This module is import-light on purpose (stdlib only): ops/amp/monitor
modules import it at module scope.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Dict, Optional

__all__ = ["Flag", "FLAGS", "register_flag", "flag_bool", "flag_int",
           "flag_float", "flag_str", "flag_value", "render_flag_table"]

_TRUE = ("1", "true", "on", "yes")
_FALSE = ("0", "false", "off", "no")


class FlagValueError(ValueError):
    """A set environment flag failed to parse/validate."""


@dataclasses.dataclass(frozen=True)
class Flag:
    """One environment flag: the registry row and its parser."""

    name: str
    kind: str                    # 'bool' | 'int' | 'float' | 'str'
    default: Any
    doc: str
    lo: Optional[float] = None
    hi: Optional[float] = None
    multiple_of: Optional[int] = None

    def parse(self, raw: str) -> Any:
        val = self._convert(raw)
        if self.lo is not None and val < self.lo:
            raise FlagValueError(
                f"{self.name}={raw!r}: {val} below minimum {self.lo}")
        if self.hi is not None and val > self.hi:
            raise FlagValueError(
                f"{self.name}={raw!r}: {val} above maximum {self.hi}")
        if self.multiple_of is not None and val % self.multiple_of:
            raise FlagValueError(
                f"{self.name}={raw!r}: {val} must be a multiple of "
                f"{self.multiple_of}")
        return val

    def _convert(self, raw: str) -> Any:
        raw = raw.strip()
        if self.kind == "bool":
            low = raw.lower()
            if low in _TRUE:
                return True
            if low in _FALSE:
                return False
            raise FlagValueError(
                f"{self.name}={raw!r} is not a boolean "
                f"(use one of {_TRUE + _FALSE})")
        if self.kind == "int":
            try:
                return int(raw)
            except ValueError:
                raise FlagValueError(
                    f"{self.name}={raw!r} is not an integer") from None
        if self.kind == "float":
            try:
                val = float(raw)
            except ValueError:
                raise FlagValueError(
                    f"{self.name}={raw!r} is not a number") from None
            if not math.isfinite(val):
                # NaN slips every range check (nan < lo is False) and
                # poisons downstream comparisons silently
                raise FlagValueError(
                    f"{self.name}={raw!r} must be finite")
            return val
        return raw                                    # 'str'

    @property
    def default_str(self) -> str:
        if self.default is None:
            return "unset"
        if self.kind == "bool":
            return "1" if self.default else "0"
        return str(self.default)


FLAGS: Dict[str, Flag] = {}


def register_flag(name: str, kind: str, default: Any, doc: str,
                  **constraints) -> Flag:
    if kind not in ("bool", "int", "float", "str"):
        raise ValueError(f"unknown flag kind {kind!r}")
    if name in FLAGS:
        raise ValueError(f"duplicate flag registration: {name}")
    flag = Flag(name=name, kind=kind, default=default, doc=doc,
                **constraints)
    FLAGS[name] = flag
    return flag


def flag_value(name: str) -> Any:
    """Parsed value of a registered flag: the environment if set (with
    validation), else the registered default."""
    flag = FLAGS.get(name)
    if flag is None:
        raise KeyError(
            f"{name} is not a registered apex_tpu flag; declare it in "
            f"apex_tpu/analysis/flags.py (the registry is the single "
            f"source of truth for the docs table and the linter)")
    raw = os.environ.get(name)
    if raw is None:
        return flag.default
    return flag.parse(raw)


def _typed(name: str, kind: str) -> Any:
    flag = FLAGS.get(name)
    if flag is not None and flag.kind != kind:
        raise TypeError(f"{name} is a {flag.kind} flag, not {kind}")
    return flag_value(name)


def flag_bool(name: str) -> bool:
    return _typed(name, "bool")


def flag_int(name: str) -> int:
    return _typed(name, "int")


def flag_float(name: str) -> float:
    return _typed(name, "float")


def flag_str(name: str) -> Optional[str]:
    return _typed(name, "str")


def render_flag_table() -> str:
    """Markdown table of the registry, stable ordering — embedded in
    docs/api/ops.md between the flag-table markers and drift-guarded by
    ci.sh step 6."""
    lines = ["| Flag | Type | Default | Constraints | Meaning |",
             "|---|---|---|---|---|"]
    for name in sorted(FLAGS):
        f = FLAGS[name]
        cons = []
        if f.lo is not None:
            cons.append(f">= {f.lo:g}")
        if f.hi is not None:
            cons.append(f"<= {f.hi:g}")
        if f.multiple_of is not None:
            cons.append(f"multiple of {f.multiple_of}")
        lines.append(
            f"| `{name}` | {f.kind} | `{f.default_str}` | "
            f"{', '.join(cons) or '—'} | {f.doc} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The registry.  Every APEX_TPU_* flag the repo reads, in one place.
# ---------------------------------------------------------------------------

register_flag(
    "APEX_TPU_FUSED_PIPELINE", "bool", True,
    "Persistent packed optimizer pipeline under amp master weights "
    "(docs/api/optimizers.md#persistent-packed-pipeline). `0` is the "
    "escape hatch back to the per-stage unscale/check/step path.")
register_flag(
    "APEX_TPU_PIPELINE_PALLAS", "bool", False,
    "Route both fused-pipeline sweeps through the Pallas kernels "
    "instead of the jnp twins (auto stays jnp per the measured "
    "880-vs-190 GB/s elementwise-stream gap).")
register_flag(
    "APEX_TPU_PIPELINE_PACK_MIN_BYTES", "int", 1 << 27,
    "Packed-size cutoff (bytes of the model-dtype tree) below which "
    "the auto pipeline decision (AmpOptimizer(pipeline=None)) routes "
    "to direct per-leaf staged updates instead of the persistent "
    "packed pipeline — the measured 0.73x small-tree packing residue "
    "regime.  Explicit pipeline=True bypasses the cutoff; 0 packs "
    "every tree.", lo=0)
register_flag(
    "APEX_TPU_STEP_PALLAS_MIN", "int", 0,
    "Element-count floor above which single-pass STEP optimizer work "
    "(adam_step/sgd_step) dispatches the Pallas kernels; 0 keeps the "
    "measured-faster XLA fusion path.", lo=0)
register_flag(
    "APEX_TPU_MOE_FUSED_DISPATCH", "bool", True,
    "Route MoE token dispatch through the fused Pallas routing + "
    "capacity-drop kernel (apex_tpu/ops/moe_routing.py: softmax -> "
    "top-k -> cumsum slotting -> buffer scatter in one VMEM pass, jnp "
    "twin off TPU) instead of the legacy one-hot einsum/scatter "
    "formulation.  Routing decisions are bit-identical either way; "
    "`0` is the escape hatch back to the unfused path.")
register_flag(
    "APEX_TPU_MOE_A2A_CHUNKS", "int", 2,
    "Capacity-chunk count for the expert-parallel all-to-all overlap "
    "(transformer/expert_parallel.py): N>=2 splits the dispatch "
    "buffer along capacity and double-buffers chunk i+1's all_to_all "
    "against chunk i's expert matmul, hiding dispatch latency behind "
    "compute (the APX704 overlap advisory goes quiet).  1 restores "
    "the legacy single-shot exchange.  Clamped to the capacity; "
    "ExpertParallelMLP.mesh_plan re-prices the collective budget "
    "accordingly.", lo=1, hi=64)
register_flag(
    "APEX_TPU_DIRECT_MIN_ELEMS", "int", 0,
    "Element-count threshold below which multi-tensor ops pack leaves "
    "into flat buffers (legacy per-step packed path); 0 keeps every "
    "leaf on the native per-leaf path.", lo=0)
register_flag(
    "APEX_TPU_FLASH_BLOCK_Q", "int", 1024,
    "Flash-attention query block rows (read at import; bench-driven "
    "re-tuning knob).", lo=8, hi=4096)
register_flag(
    "APEX_TPU_FLASH_BLOCK_K", "int", 1024,
    "Flash-attention key block columns (read at import).", lo=8, hi=4096)
register_flag(
    "APEX_TPU_FLASH_PACK_D64", "bool", True,
    "d=64 head-pair packing into full 128-lane MXU tiles "
    "(docs/api/ops.md head-packing note). `0` forces the half-width "
    "per-head kernels.")
register_flag(
    "APEX_TPU_FLASH_E_MAX_SEQ", "int", 32768,
    "Longest padded sequence the blocked E-layout flash walk streams "
    "before falling back to the transposing path (bounds the "
    "lse/delta sideband HBM).", lo=128, hi=1 << 20)
register_flag(
    "APEX_TPU_FLASH_E_BLOCK", "int", 512,
    "E-layout flash walk block size (TPU lane grain).", lo=128, hi=4096, multiple_of=128)
register_flag(
    "APEX_TPU_FLASH_E_LANES", "int", 768,
    "Lane budget per head-group block in the E-layout kernels (VMEM "
    "sizing for the bwd score temporaries).", lo=8, hi=4096)
register_flag(
    "APEX_TPU_MONITOR_JSONL", "str", None,
    "Path for an apex_tpu.monitor JSONL event log in drivers that "
    "support ambient wiring (e.g. the 3D-parallel convergence runner).")
register_flag(
    "APEX_TPU_MONITOR_STALL_S", "float", 300.0,
    "Watchdog stall timeout (seconds) for ambient monitor wiring.", lo=0.0)
register_flag(
    "APEX_TPU_TRACE_DIR", "str", None,
    "Ambient wall-time tracing directory (apex_tpu.monitor.tracing): "
    "drivers that support it (the convergence runner) record host "
    "spans + the per-step waterfall and write trace.chrome.json "
    "there.  The smoke drivers take --trace DIR explicitly.")
register_flag(
    "APEX_TPU_TRACE_CAPTURE_FILE", "str", None,
    "On-demand capture trigger: touching this file at a step boundary "
    "opens a pyprof.ProfileWindow for APEX_TPU_TRACE_CAPTURE_STEPS "
    "steps (the file is consumed; one window per touch).")
register_flag(
    "APEX_TPU_TRACE_CAPTURE_STEPS", "int", 4,
    "Length (steps) of an on-demand / auto capture window.", lo=1)
register_flag(
    "APEX_TPU_TRACE_RATIO_MIN", "float", 0.0,
    "Auto-capture threshold: a step whose wall_device_ratio falls "
    "below this opens one profiling window (0 disables; the "
    "waterfall sibling of the Watchdog stall-trace hook).",
    lo=0.0, hi=1.0)
register_flag(
    "APEX_TPU_TELEMETRY_DRAIN_EVERY", "int", 0,
    "Deferred-telemetry cadence for the smoke drivers: K>=1 "
    "accumulates per-step scalars in a device ring "
    "(monitor.tracing.DeviceMetricsBuffer) drained every K steps — "
    "zero per-step host transfers; 0 keeps the classic synchronous "
    "per-step readback.", lo=0)
register_flag(
    "APEX_TPU_SCAN_STEPS", "int", 0,
    "Batched-step scan driver for the smoke drivers: K>=1 runs K train "
    "steps per jit call via lax.scan (params/amp state/telemetry ring "
    "threaded through the carry, all donated), amortizing per-dispatch "
    "host overhead across the window; telemetry drains and checkpoint/"
    "watchdog/waterfall boundaries land on K-step edges.  0 keeps the "
    "classic one-dispatch-per-step loop.  The smoke drivers' "
    "--scan-steps overrides.", lo=0)
register_flag(
    "APEX_TPU_COMPILE_CACHE_DIR", "str", None,
    "Persistent XLA compilation cache directory "
    "(utils.compile_cache.configure_compile_cache): when set, every "
    "driver/bench process wires jax's persistent cache here (size/"
    "compile-time floors relaxed so even smoke-sized programs cache), "
    "so a warmed host pays compile cost once — cold-start and retrace "
    "stop polluting wall rows.  One `python -m "
    "apex_tpu.testing.entry_points --aot` run pre-populates it for "
    "every registered entry point.")
register_flag(
    "APEX_TPU_SERVE_KV_BLOCK", "int", 16,
    "Tokens per KV-cache block in the serving stack "
    "(docs/api/serving.md): the paging grain the flash-decode kernel "
    "gathers by and the unit the block pool allocates.  128 matches "
    "the MXU lane width on a real TPU; the smoke/CI default keeps "
    "tiny prompts multi-page so the paging paths are exercised.",
    lo=1, hi=4096)
register_flag(
    "APEX_TPU_SERVE_KV_DTYPE", "str", "model",
    "KV-cache storage dtype: 'model' stores k/v in the model compute "
    "dtype, 'bf16' forces bfloat16, 'int8' stores weight-only-"
    "quantized rows with per-token fp32 scales (appending never "
    "requantizes history; the kernel dequantizes per page in VMEM).")
register_flag(
    "APEX_TPU_SERVE_BLOCKS", "int", 64,
    "KV-cache pool size (blocks, INCLUDING the reserved dump block 0) "
    "for drivers that size the cache from flags (standalone_gpt "
    "--serve); engine callers may pass an explicit pool.", lo=2)
register_flag(
    "APEX_TPU_SERVE_BATCH_BUCKETS", "str", "1,2,4,8",
    "Registered decode batch-size ladder (comma-separated, "
    "ascending): a decode step's batch rounds up to the smallest "
    "rung, so steady-state serving compiles exactly one program per "
    "(batch, pages) bucket — the recompile budget sanitize() "
    "enforces.")
register_flag(
    "APEX_TPU_SERVE_PAGE_BUCKETS", "str", "1,2,4,8",
    "Registered page-span ladder: the decode step's block-table "
    "width (and the prefill padding, in blocks) rounds up to the "
    "smallest rung.  max rung x APEX_TPU_SERVE_KV_BLOCK bounds the "
    "servable sequence length.")
register_flag(
    "APEX_TPU_SERVE_SPECULATE_K", "int", 0,
    "Speculative decoding for the serving engine "
    "(docs/api/serving.md#speculative-decoding): K>=1 has the draft "
    "model propose K tokens per tick, the target model score all of "
    "them in ONE multi-token paged-attention call, and greedy-match "
    "acceptance keep the longest agreeing prefix plus one corrected "
    "token — output is token-for-token identical to non-speculative "
    "greedy decode; rejected tokens roll the KV write cursor back.  "
    "0 disables (one target call, one token per tick).  Requires a "
    "draft model (standalone_gpt --serve --speculate-k builds one).",
    lo=0, hi=16)
register_flag(
    "APEX_TPU_SERVE_PREFILL_CHUNK", "int", 0,
    "Chunked prefill (docs/api/serving.md#chunked-prefill): N>=1 "
    "splits prompt prefill into N-token chunks interleaved one per "
    "engine tick with running requests' decode steps, bounding the "
    "ITL spike a long-prompt admission inflicts.  The chunk size is "
    "a bucket dimension (AOT-warmed like the rest of the ladder, so "
    "the zero-steady-state-recompile contract holds).  0 prefills "
    "whole prompts synchronously at admission.", lo=0)
register_flag(
    "APEX_TPU_SERVE_PREFIX_SHARE", "bool", False,
    "Copy-on-write prompt-prefix sharing in the serving KV pool "
    "(docs/api/serving.md#prefix-sharing): full prompt blocks are "
    "content-chain-hashed into a shared read-only page index with "
    "refcounts; a warm prefix maps shared pages instead of "
    "re-prefilling them (prefill runs only on the unshared tail, and "
    "admission reserves only the tail), eviction parks zero-ref "
    "blocks in an idle LRU reclaimed under pool pressure, and any "
    "write into a shared page copies it first.")
register_flag(
    "APEX_TPU_SERVE_TICK_EVERY", "int", 1,
    "Engine-gauge cadence for the serving telemetry layer "
    "(serving/metrics.py): one kind=\"serve_tick\" event leaves every "
    "K engine ticks, carrying running batch, active bucket shape, "
    "free/reserved blocks, queue depth, and the window's admissions/"
    "evictions/preemptions/compiles — the feed a fleet router "
    "load-balances on.  Counters accumulate across the window; a "
    "trailing partial window flushes at run end.", lo=1)
register_flag(
    "APEX_TPU_SERVE_DEADLINE_MS", "float", 0.0,
    "Default request deadline (milliseconds, submit -> last token) "
    "for serving requests that do not carry their own: a queued "
    "request past its deadline is expired terminal "
    "`deadline_exceeded`, a running one evicted terminal `deadline` "
    "(blocks freed) — enforced at tick boundaries, AFTER the "
    "expiring tick's tokens were delivered.  0 disables "
    "(docs/api/resilience.md#serving-resilience).", lo=0.0)
register_flag(
    "APEX_TPU_SERVE_SHED_POOL_HW", "float", 0.0,
    "Load-shedding high-water mark on KV-pool pressure (fraction of "
    "usable blocks an allocation could not draw on): crossing it "
    "engages shedding — admissions stop and lowest-priority/"
    "shortest-progress work sheds — until pressure drops below the "
    "low-water mark (high-water minus 0.15, the hysteresis band).  "
    "0 disables the pool trigger.", lo=0.0, hi=1.0)
register_flag(
    "APEX_TPU_SERVE_SHED_QUEUE_HW", "int", 0,
    "Load-shedding high-water mark on the admission backlog (queued "
    "+ mid-prefill requests): crossing it engages shedding until the "
    "backlog drops below half the mark (hysteresis).  0 disables the "
    "queue trigger.", lo=0)
register_flag(
    "APEX_TPU_SERVE_JOURNAL_DIR", "str", None,
    "Directory for the serving request journal "
    "(serving/resilience.py): when set, the --serve driver records "
    "every request's submit/progress/terminal transitions to "
    "<dir>/serve.journal.jsonl (crash-safe append-only JSONL), and a "
    "supervised serve (--supervise) replays it after an engine-loop "
    "crash — every non-terminal request re-submitted, warm through "
    "prefix sharing.  The --journal CLI flag overrides.")
register_flag(
    "APEX_TPU_SERVE_SNAPSHOT_FILE", "str", None,
    "On-demand serving snapshot trigger: touching this file (or "
    "SIGUSR1 in the --serve driver) dumps the live engine state — "
    "queue depth, active requests and their progress, pool/"
    "reservation bookkeeping, compile counts — as ONE engine_snapshot "
    "JSON event at the next tick boundary (the file is consumed; "
    "exactly one snapshot per trigger).  The wedged-serve "
    "post-mortem hook (docs/api/serving.md).")
register_flag(
    "APEX_TPU_SERVE_REPLICAS", "int", 1,
    "Fleet size for the multi-replica serving driver (standalone_gpt "
    "--serve-fleet / docs/api/serving.md#fleet-serving): N "
    "ServingEngine replicas behind the gauge-fed FleetRouter, each "
    "with its own KV pool (and, with APEX_TPU_SERVE_TP, its own "
    "device slice).  The --replicas CLI flag overrides.", lo=1,
    hi=64)
register_flag(
    "APEX_TPU_SERVE_TP", "int", 0,
    "Tensor-parallel decode width per serving replica "
    "(serving/tp.py): T>=2 shards weights and the paged KV cache "
    "along a MeshPlan `tensor` axis (head-sharded attention, "
    "column/row-split MLP, 2 psums per layer — the audited "
    "gpt_decode_step_tp topology), greedy output token-identical to "
    "the single-chip engine.  0/1 keeps single-chip replicas.  The "
    "--tp CLI flag overrides.", lo=0, hi=64)
register_flag(
    "APEX_TPU_SERVE_EP", "int", 0,
    "Expert-parallel decode width for the serving engine "
    "(serving/ep.py): E>=2 shards a MoE model's expert weights along "
    "a MeshPlan `expert` axis (attention and the paged KV cache "
    "replicated, per-rank token slices routed through the overlapped "
    "all-to-all exchange — the audited gpt_decode_step_ep topology), "
    "greedy output token-identical to the dense single-chip engine "
    "on a 1-expert config.  0/1 keeps single-chip decode.  The --ep "
    "CLI flag overrides.", lo=0, hi=64)
register_flag(
    "APEX_TPU_SERVE_DISAGGREGATE", "bool", False,
    "Disaggregated prefill/decode for the serving fleet: prefill-role "
    "replicas run prompt admission only and stream finished KV blocks "
    "(block table as the wire format, int8/bf16 storage preserved) "
    "into decode replicas' paged pools, registered into the shared "
    "prefix index so the decode-side admission is warm "
    "(prefix_hit_tokens > 0).  Requires APEX_TPU_SERVE_PREFIX_SHARE "
    "semantics on every replica (the fleet driver arms it).  The "
    "--disaggregate CLI flag overrides.")
register_flag(
    "APEX_TPU_SERVE_ROUTER", "str", "gauges",
    "FleetRouter submission policy: 'gauges' scores replicas by the "
    "router_snapshot feed — sticky warm-prefix affinity first (chain-"
    "key intersection with each replica's shared index), then pool "
    "headroom net of in-flight reservations, then smallest backlog, "
    "avoiding shed-engaged replicas; 'round_robin' ignores all "
    "signals (the A/B control).")
register_flag(
    "APEX_TPU_METRICS_PORT", "int", 0,
    "Live metrics plane (monitor/export.py): >0 starts the stdlib "
    "MetricsServer daemon thread on this port for the --serve / "
    "--serve-fleet drivers, exposing /metrics (Prometheus text "
    "exposition fed from the existing gauge/metrics structures — no "
    "second bookkeeping path), /healthz (drain/shed/escalation/"
    "SLO-burn aware, 503 while draining) and /varz (the same "
    "engine.snapshot_state() JSON as the SIGUSR1 trigger).  0 "
    "disables.  The --metrics-port CLI flag overrides; port 0 with "
    "the CLI flag picks an ephemeral port (printed in the "
    "metrics_server_started event).", lo=0, hi=65535)
register_flag(
    "APEX_TPU_CP_RPC_TIMEOUT_S", "float", 60.0,
    "Process-isolated control plane (serving/control_plane.py): "
    "per-attempt socket deadline in seconds for replica RPCs that "
    "carry work (tick/submit/gather/scatter).  A timed-out "
    "non-idempotent op escalates to SIGKILL + respawn + journal "
    "replay rather than a blind resend.  The ProcessFleet "
    "rpc_timeout_s ctor argument overrides.", lo=0.1)
register_flag(
    "APEX_TPU_CP_POLL_TIMEOUT_S", "float", 10.0,
    "Control plane gauge-poll deadline in seconds for the per-round "
    "router_snapshot RPC.  A timed-out poll never blocks the tick: "
    "the replica keeps its stale snapshot, its router score degrades "
    "(stale replicas sort last), and a heartbeat miss is charged.  "
    "The ProcessFleet poll_timeout_s ctor argument overrides.",
    lo=0.1)
register_flag(
    "APEX_TPU_CP_RPC_RETRIES", "int", 2,
    "Control plane retry budget for idempotent replica RPCs "
    "(snapshot/gather/summary/shutdown).  Each retry re-sends under "
    "a fresh sequence number after a bounded backoff; non-idempotent "
    "ops always run with zero retries and escalate to restart+replay "
    "instead.  The ProcessFleet rpc_retries ctor argument overrides.",
    lo=0, hi=16)
register_flag(
    "APEX_TPU_CP_SPAWN_TIMEOUT_S", "float", 300.0,
    "Control plane replica spawn deadline in seconds: the supervisor "
    "waits this long for a freshly spawned subprocess to connect its "
    "socket and send the hello frame (covers jax import + engine "
    "build + journal replay).  Exceeding it kills the child and "
    "counts a restart.  The ProcessFleet spawn_timeout_s ctor "
    "argument overrides.", lo=1.0)
register_flag(
    "APEX_TPU_CP_CONNECT_TIMEOUT_S", "float", 300.0,
    "Control plane child-side rendezvous deadline in seconds: how "
    "long a freshly spawned replica keeps retrying its AF_UNIX "
    "connect before giving up.  Normally unused — begin_spawn stamps "
    "the listener's own spawn_timeout_s into EngineSpec."
    "connect_timeout_s so both halves of the handshake run on one "
    "clock — this flag is the fallback for a worker entered outside "
    "ReplicaProcess (its default matches "
    "APEX_TPU_CP_SPAWN_TIMEOUT_S for the same reason).", lo=1.0)
register_flag(
    "APEX_TPU_CP_HEARTBEAT_MISSES", "int", 3,
    "Control plane liveness threshold: consecutive missed gauge "
    "polls (rpc_timeout on router_snapshot) a replica may accrue "
    "before the supervisor declares it hung, SIGKILLs it, and "
    "restarts it with journal replay under bounded backoff.  The "
    "ProcessFleet heartbeat_misses ctor argument overrides.",
    lo=1, hi=100)
register_flag(
    "APEX_TPU_SLO_TTFT_P99_MS", "float", 0.0,
    "Serving SLO: time-to-first-token p99 objective in milliseconds "
    "for ALL priority classes (serving/metrics.SLOTracker).  >0 arms "
    "dual-window burn-rate tracking — an slo_burn alarm fires "
    "(once per episode, through the watchdog escalation machinery) "
    "when both the fast and slow rolling windows burn error budget "
    "at >= the trip threshold.  0 disables the dimension.", lo=0.0)
register_flag(
    "APEX_TPU_SLO_ITL_P99_MS", "float", 0.0,
    "Serving SLO: inter-token-latency p99 objective in milliseconds, "
    "same burn-rate semantics as APEX_TPU_SLO_TTFT_P99_MS.  0 "
    "disables the dimension.", lo=0.0)
register_flag(
    "APEX_TPU_SLO_AVAILABILITY", "float", 0.0,
    "Serving SLO: availability target as a fraction (e.g. 0.999) — a "
    "request counts against it when it terminates shed or "
    "deadline_exceeded (preemptions are resumed work, not failures). "
    "Error budget is 1-target; burn-rate semantics as the latency "
    "objectives.  0 disables the dimension.", lo=0.0, hi=1.0)
register_flag(
    "APEX_TPU_SHARDING_MIN_BYTES", "int", 1024,
    "Size floor for the SPMD auditor's APX701 replication rule "
    "(docs/api/analysis.md): a plan-sharded tensor smaller than this "
    "may propagate replicated without failing — replicating a scalar "
    "step count costs nothing, and the rule exists for param/state/"
    "activation buffers whose 1/N sharding IS the memory plan.", lo=0)
register_flag(
    "APEX_TPU_SCHED_SEEDS", "int", 5,
    "Seed count for the deterministic-schedule fleet stress harness "
    "(python -m apex_tpu.analysis.schedule, ci.sh step 13): each "
    "seed serves the same request trace on the threaded fleet under "
    "a different reproducible thread interleaving; the terminal "
    "fleet digest must be identical across all of them, with zero "
    "lost requests and zero uncaught background-thread exceptions.",
    lo=1, hi=64)
register_flag(
    "APEX_TPU_FULL", "bool", False,
    "CI switch: run the full (slow-inclusive) test tier in "
    "tools/ci.sh.")
register_flag(
    "APEX_TPU_L1_FULL", "bool", False,
    "Run the full L1 amp x optimizer cross-product grid instead of "
    "the CI slice.")
