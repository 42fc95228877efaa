"""Continuous-batching serving engine.

The serving loop alternates two worlds on a fixed cadence:

* **between** jitted steps (host, this module): finished requests are
  evicted (their cache blocks return to the pool), queued requests are
  admitted (blocks allocated, prompt prefilled), and the next decode
  batch is assembled;
* **inside** jitted steps (:mod:`.model`): one prefill per admission,
  then one batched decode step per engine tick — cache donated through
  every call, greedy sampling in-graph, one int32 per row of output
  traffic.

Shapes are **bucketed**: the decode batch rounds up to a registered
batch bucket and the page span to a page bucket
(:class:`BucketLadder`, ``APEX_TPU_SERVE_BATCH_BUCKETS`` /
``APEX_TPU_SERVE_PAGE_BUCKETS``), prompt lengths to page-bucket
multiples of the block size — so the set of compiled programs is the
(small, finite) ladder product, every member AOT-compiled by
:meth:`ServingEngine.warmup` before traffic.  Steady-state serving
under :func:`apex_tpu.analysis.sanitize` therefore compiles exactly
once per bucket and never again — the same recompile budget the
training smoke enforces, now on the serving path (the tests and
tools/ci.sh step 10 prove it).

Admission control is **reservation-based**: a request is admitted only
when the pool can cover its whole worst case (prompt + max new
tokens), so a mid-flight decode can never exhaust the pool — eviction
is always "request finished", never "victim chosen".  Utilization-
optimistic admission (overcommit + preempt) layers on top of the same
pool primitives; this engine ships the safe policy.

Per-token latency is the engine tick wall (each active request gains
one token per tick); the run summary reports p50/p99 over every
generated token plus decode tokens/s — the rows ``standalone_gpt
--serve`` prints.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import Counter, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.flags import flag_bool, flag_float, flag_int, flag_str
from ..monitor.export import MetricsRegistry
from ..monitor.tracing import recording, span, watch_collector
from ..utils.log_util import get_logger
from .kv_cache import (DUMP_BLOCK, KVCacheConfig, KVCacheManager,
                       PrefixMatch, init_cache)
from .metrics import ServeMetrics, SLOTracker
from ..ops.quant_matmul import is_quantized_weights
from .model import (MOE_TICK_COUNTERS, GPTServingWeights,
                    ServingModelConfig,
                    copy_cache_block, decode_plans, gpt_decode_step,
                    gpt_extend_step, gpt_prefill_step,
                    mtp_extend_step, mtp_prefill_step,
                    weights_in_compute_dtype)
from .resilience import RequestJournal, ShedPolicy, SpeculationGovernor

logger = get_logger(__name__)

__all__ = ["Request", "BucketLadder", "ServingEngine", "ServeSummary",
           "default_cache_config"]

# per-token latency samples kept for the p50/p99 window (a lifetime
# list would grow without bound on a long-running serve)
_LATENCY_WINDOW = 100_000

# the vectors a decode tick hands its step a row of the batch, in the
# order the pooled cache's step unpacks them: tokens, positions,
# seq_lens, write_blocks, write_offsets, pool_blocks, pool_offsets
_POOLED_TICK_ROWS = 7

# the gauges of ``_tick_tail`` that ride on the ``apex.serve.step`` span
# as its metadata (with ``admitted``), set only while a trace records
_STEP_SPAN_COUNTERS = ("batch", "batch_bucket", "pages_bucket",
                       "queue_depth", "used_blocks", "pool_blocks")


def _parse_ladder(raw: str) -> Tuple[int, ...]:
    vals = tuple(sorted({int(x) for x in raw.split(",") if x.strip()}))
    if not vals or vals[0] < 1:
        raise ValueError(f"bucket ladder {raw!r} must name positive "
                         f"integers")
    return vals


@dataclasses.dataclass(frozen=True)
class BucketLadder:
    """The registered (batch, pages[, prefill-chunk]) shape ladder.
    ``pick`` rounds a live size up to the smallest rung, so
    steady-state serving runs a finite, precompilable program set.
    ``chunks`` is the prefill-chunk token dimension (ISSUE-12): empty
    means "derive from the page rungs" (one whole-padded-prompt chunk
    per page rung — the warm-tail prefill shape when chunked prefill
    is off); the ``APEX_TPU_SERVE_PREFILL_CHUNK`` flag registers a
    single explicit rung."""

    batch: Tuple[int, ...]
    pages: Tuple[int, ...]
    chunks: Tuple[int, ...] = ()

    @classmethod
    def from_flags(cls) -> "BucketLadder":
        chunk = flag_int("APEX_TPU_SERVE_PREFILL_CHUNK")
        return cls(
            batch=_parse_ladder(flag_str("APEX_TPU_SERVE_BATCH_BUCKETS")),
            pages=_parse_ladder(flag_str("APEX_TPU_SERVE_PAGE_BUCKETS")),
            chunks=(chunk,) if chunk > 0 else ())

    @staticmethod
    def _pick(rungs: Tuple[int, ...], n: int, what: str) -> int:
        for r in rungs:
            if n <= r:
                return r
        raise ValueError(f"{what} {n} exceeds the ladder {rungs} — "
                         f"register a bigger rung or admit less")

    def pick_batch(self, n: int) -> int:
        return self._pick(self.batch, n, "batch size")

    def pick_pages(self, n: int) -> int:
        return self._pick(self.pages, n, "page span")

    def chunk_rungs(self, block_size: int) -> Tuple[int, ...]:
        """The effective prefill-chunk rungs: the registered ones, or
        (when none are) a derived set — one single-block rung (the
        common warm-prefix tail is a handful of tokens; padding it to
        the full page span would cost a whole prefill) plus one
        whole-padded-prompt rung per page bucket for long unshared
        tails — so a warm-tail prefill has a compiled shape even with
        chunked prefill disabled."""
        if self.chunks:
            return self.chunks
        return tuple(sorted({block_size}
                            | {p * block_size for p in self.pages}))

    def pick_chunk(self, n: int, block_size: int) -> int:
        """Round a chunk of ``n`` tokens up to the smallest chunk
        rung; a tail longer than every rung processes the largest
        rung per tick (the caller loops)."""
        rungs = self.chunk_rungs(block_size)
        for r in rungs:
            if n <= r:
                return r
        return rungs[-1]

    @property
    def max_batch(self) -> int:
        return self.batch[-1]

    @property
    def max_pages(self) -> int:
        return self.pages[-1]


@dataclasses.dataclass
class Request:
    """One generation request and its accumulated results.

    ``deadline_ms`` bounds the request's whole wall (submit → last
    token) relative to its submit instant: a queued request past its
    deadline is expired with terminal ``deadline_exceeded``; a running
    one is evicted with terminal ``deadline`` — both at tick
    boundaries, AFTER the expiring tick's tokens were delivered (the
    deadline-at-boundary semantics the tests pin).  ``None`` falls
    back to the engine default (``APEX_TPU_SERVE_DEADLINE_MS``, 0 =
    no deadline).  ``priority`` orders load shedding: under pool/queue
    pressure the :class:`~.resilience.ShedPolicy` sheds lowest
    priority, shortest progress first."""

    rid: Any
    prompt: List[int]
    max_new_tokens: int
    eos_token: Optional[int] = None
    deadline_ms: Optional[float] = None
    priority: int = 0
    # engine-owned:
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    token_latency_s: List[float] = dataclasses.field(
        default_factory=list)
    admitted_at_step: Optional[int] = None
    preempted: bool = False
    submit_t: Optional[float] = None     # engine-clock submit instant
    terminal: Optional[str] = None       # finished | preempted |
    # deadline | deadline_exceeded | shed — set exactly once
    draft: Optional[int] = None          # the model's own MTP module's
    # proposal for the token after the next (speculation by MTP)

    @property
    def done(self) -> bool:
        if self.out_tokens and self.eos_token is not None \
                and self.out_tokens[-1] == self.eos_token:
            return True
        return len(self.out_tokens) >= self.max_new_tokens


@dataclasses.dataclass
class ServeSummary:
    """What a serve run measured (the --serve row source)."""

    requests_done: int
    requests_preempted: int
    tokens_generated: int
    prefill_tokens: int
    wall_s: float
    decode_steps: int
    tokens_per_sec: float
    # decode ticks only (prefill wall excluded) — the honest basis for
    # kernel-vs-baseline decode comparisons
    decode_wall_s: float
    decode_tokens_per_sec: float
    latency_p50_ms: Optional[float]
    latency_p99_ms: Optional[float]
    compiles: Dict[str, int]
    drained: bool = False
    # per-request lifecycle distributions (serving/metrics.py, bounded
    # windows): admission queue wait, time-to-first-token, and
    # inter-token latency percentiles; None until a series has samples
    queue_wait_p50_ms: Optional[float] = None
    queue_wait_p99_ms: Optional[float] = None
    ttft_p50_ms: Optional[float] = None
    ttft_p99_ms: Optional[float] = None
    itl_p50_ms: Optional[float] = None
    itl_p99_ms: Optional[float] = None
    # submits the engine refused, by reason (ladder_span / max_seq /
    # empty_prompt / max_new_tokens) — rejected requests never enter
    # the queue and never get lifecycle chains
    requests_rejected: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # ISSUE-12 decode fast path, all printed numbers (the ROADMAP
    # exit criteria), not derived ones: speculative-decode acceptance
    # (None when speculation is off), prompt-prefix sharing
    # (warm admissions, prefill tokens skipped, shared-block
    # high-water, copy-on-write count), and chunked-prefill volume
    spec_accept_rate: Optional[float] = None
    spec_tokens_proposed: int = 0
    spec_tokens_accepted: int = 0
    warm_prefix_admissions: int = 0
    prefix_hit_tokens: int = 0
    shared_blocks_hw: int = 0
    cow_copies: int = 0
    prefill_chunks: int = 0
    # ISSUE-13 serving resilience: requests expired past their
    # deadline (queued OR running), requests shed under pool/queue
    # pressure, how often the shed policy engaged, whether the
    # speculation governor degraded the run, and how many requests
    # entered through a journal replay (supervised crash recovery)
    requests_deadline: int = 0
    requests_shed: int = 0
    shed_engagements: int = 0
    spec_disabled: bool = False
    replayed_requests: int = 0
    # how many crash recoveries (engine.crash_reset) produced this
    # summary — counted on the engine itself so the serve_done event
    # carries the real value, not a post-hoc patch (0 = never crashed)
    restarts: int = 0
    # ISSUE-17 live metrics plane: SLO burn-rate episodes this engine
    # tripped (and recovered from), plus the class/dimension pairs
    # still burning when the summary was taken — the SERVE_DONE
    # surface of the SLOTracker (None objectives => all zeros)
    slo_burn_episodes: int = 0
    slo_recoveries: int = 0
    slo_burning: List[str] = dataclasses.field(default_factory=list)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _percentile(xs: Sequence[float], q: float) -> Optional[float]:
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


@dataclasses.dataclass
class _PrefillJob:
    """An admitted request whose prompt k/v is still being written —
    blocks already owned (alloc'd at admission, shared prefix mapped),
    ``written`` positions valid so far.  Advanced one chunk per engine
    tick (chunked prefill) or drained synchronously at admission (the
    warm-tail path when chunking is off)."""

    req: Request
    tokens: np.ndarray            # the whole prompt, int32
    written: int                  # k/v-valid positions so far
    start: int                    # prefix-shared positions (skipped)
    admit_t: float                # prefill-start instant


class ServingEngine:
    """Continuous-batching driver over one model + one paged cache.

    ``weights``/``model_cfg`` come from :mod:`.model`;
    ``cache_cfg`` sizes the pool.  The engine holds the weights in the
    dtype its programs read them in
    (:func:`~.model.weights_in_compute_dtype`, applied once to every
    tree it takes: here, in :meth:`swap_weights`, and to the draft's):
    a float32 GPT tree under a bf16 policy is cast leaf by leaf, a tree
    already in that dtype is held as the arrays given.  The caller's
    tree is not touched and may be dropped; the ``weights_held`` event
    and ``router_snapshot()["weight_bytes"]`` say what is held.
    ``monitor`` is an optional
    :class:`apex_tpu.monitor.StepMonitor` (or anything with its
    ``event`` method) receiving ``serving`` events; ``autoresume`` an
    installed :class:`apex_tpu.resilience.AutoResume` polled between
    steps for the SIGTERM clean-drain path.

    Long-running serves: summary totals come from lifetime counters
    and latency percentiles from a bounded window of the most recent
    samples, so a caller may drain ``done`` (pop finished requests)
    at any time to keep host memory flat without corrupting the
    summary."""

    def __init__(self, weights: GPTServingWeights,
                 model_cfg: ServingModelConfig,
                 cache_cfg: KVCacheConfig, *,
                 ladder: Optional[BucketLadder] = None,
                 monitor=None, autoresume=None,
                 tick_every: Optional[int] = None,
                 snapshot=None,
                 speculate_k: Optional[int] = None,
                 draft_weights: Optional[GPTServingWeights] = None,
                 draft_cfg: Optional[ServingModelConfig] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_share: Optional[bool] = None,
                 deadline_ms: Optional[float] = None,
                 shed: Optional[ShedPolicy] = None,
                 journal: Optional[RequestJournal] = None,
                 escalation=None, fault=None,
                 spec_governor="auto",
                 tp=None, ep=None,
                 replica_id: Optional[str] = None,
                 device=None,
                 slo="auto", exporter=None,
                 clock: Callable[[], float] = time.perf_counter):
        # --- ISSUE-14 fleet hooks -----------------------------------
        # ``tp`` is a serving.tp.TPContext: the engine swaps its jit
        # builders for the shard_map-wrapped TP ones, commits weights
        # and cache to the plan's shardings, and serves with the
        # tp-axis-carrying model config — the continuous-batching loop
        # is otherwise unchanged.  ``replica_id`` stamps every emitted
        # event with a stable fleet identity (ReplicaMonitor).
        # ``device`` pins a single-chip replica's weights and cache to
        # one device, so N fleet replicas execute on N device streams
        # CONCURRENTLY — without it every replica's arrays land on
        # device 0 and the fleet serializes behind one stream (mutually
        # exclusive with ``tp``, whose mesh already places the shards).
        # ``ep`` is a serving.ep.EPContext (ISSUE-19): same swap, but
        # the expert stacks shard and attention/cache replicate — the
        # MoE decode fast path.  tp/ep/device are mutually exclusive;
        # a context owns its device slice.
        self.tp = tp
        self.ep = ep
        self.device = device
        if sum(x is not None for x in (tp, ep, device)) > 1:
            raise ValueError("pass at most one of tp, ep, device — a "
                             "context owns its device slice")
        self.replica_id = (str(replica_id) if replica_id is not None
                           else None)
        if self.replica_id is not None and monitor is not None:
            from .metrics import ReplicaMonitor

            if not isinstance(monitor, ReplicaMonitor):
                monitor = ReplicaMonitor(monitor, self.replica_id)
        # the engine OWNS its weights in the dtype the steps read them
        # in (model.weights_in_compute_dtype: cast once here, not by
        # every program); what that did, by tree, for ``weights_held``
        self._held: Dict[str, Dict[str, int]] = {}
        weights, self._held["target"] = _held_weights(weights, model_cfg)
        if tp is not None:
            if speculate_k or draft_weights is not None:
                raise ValueError(
                    "tensor-parallel serving does not compose with "
                    "speculative decoding yet — run the draft on its "
                    "own replica or drop one of the two")
            if tp.cache_cfg != cache_cfg:
                raise ValueError(
                    "TPContext was built for a different cache "
                    "config than the engine's")
            # int8 weights need the plan's scale-row specs armed; a
            # context built for the other weight format rebinds here
            # so callers never hand-sync the flag
            if tp.weight_quantized != is_quantized_weights(weights):
                self.tp = tp = tp.rebind(
                    weight_quantized=is_quantized_weights(weights))
            model_cfg = tp.model_cfg       # tp_axis armed
            weights = tp.shard_weights(weights)
        elif ep is not None:
            if speculate_k or draft_weights is not None:
                raise ValueError(
                    "expert-parallel serving does not compose with "
                    "speculative decoding yet — run the draft on its "
                    "own replica or drop one of the two")
            if ep.cache_cfg != cache_cfg:
                raise ValueError(
                    "EPContext was built for a different cache "
                    "config than the engine's")
            if is_quantized_weights(weights):
                raise ValueError(
                    "expert-parallel serving does not take int8 "
                    "weights yet — the Q8 kernel has no expert-stack "
                    "layout; serve bf16 or use tp")
            model_cfg = ep.model_cfg       # ep_axis armed
            weights = ep.shard_weights(weights)
        elif device is not None:
            weights = jax.device_put(weights, device)
        if model_cfg.family == "rope_moe" and cache_cfg.quantized:
            raise ValueError(
                "family 'rope_moe' does not serve from an int8 cache "
                "yet: its grouped-head kernel path is unproven there")
        if cache_cfg.latent != (model_cfg.mla is not None):
            raise ValueError(
                f"family {model_cfg.family!r} and a cache with "
                f"value_dim={cache_cfg.value_dim}: latent attention "
                f"(family 'mla_moe'), and it alone, serves from the "
                f"latent cache (default_cache_config makes it)")
        eva = {(s.window, s.chunk) for s in model_cfg.layers
               if s.chunk is not None}
        if eva != ({(cache_cfg.window, cache_cfg.block_size)}
                   if cache_cfg.pooled else set()):
            raise ValueError(
                f"EVA layers (window, chunk) {sorted(eva)} and a cache of "
                f"window {cache_cfg.window}, block_size "
                f"{cache_cfg.block_size}: they, and they alone, serve "
                f"from the pooled cache of their window whose pages are a "
                f"chunk long (default_cache_config makes it)")
        self.weights = weights
        self.model_cfg = model_cfg
        self.cache_cfg = cache_cfg
        self.ladder = ladder if ladder is not None \
            else BucketLadder.from_flags()
        max_need = self.ladder.max_pages
        if max_need > cache_cfg.usable_blocks:
            raise ValueError(
                f"page ladder max {max_need} exceeds the pool's "
                f"{cache_cfg.usable_blocks} usable blocks")
        self.monitor = monitor
        self.autoresume = autoresume
        self._clock = clock
        # --- ISSUE-12 decode fast path knobs (flags unless pinned) --
        self.speculate_k = speculate_k if speculate_k is not None \
            else flag_int("APEX_TPU_SERVE_SPECULATE_K")
        self.prefill_chunk = prefill_chunk if prefill_chunk is not None \
            else flag_int("APEX_TPU_SERVE_PREFILL_CHUNK")
        if self.prefill_chunk > 0 and not self.ladder.chunks:
            self.ladder = dataclasses.replace(
                self.ladder, chunks=(self.prefill_chunk,))
        self.prefix_share = prefix_share if prefix_share is not None \
            else flag_bool("APEX_TPU_SERVE_PREFIX_SHARE")
        if cache_cfg.pooled:
            # a chunk of a prompt is a window of its own (its keys are
            # its activations), and a pooled row cannot be rolled back
            rungs = self.ladder.chunk_rungs(cache_cfg.block_size)
            if self.prefill_chunk != cache_cfg.window or any(
                    r % cache_cfg.block_size ** 2 for r in rungs) \
                    or rungs[-1] != cache_cfg.window:
                raise ValueError(
                    f"the pooled cache is prefilled a window a chunk: "
                    f"prefill_chunk {self.prefill_chunk} has to be its "
                    f"window {cache_cfg.window}, and the chunk rungs "
                    f"{rungs} whole summary pages up to it")
            if self.speculate_k or draft_weights is not None:
                raise ValueError(
                    "speculative decoding does not serve the pooled "
                    "cache yet: a pooled row cannot be taken back")
        # --- ISSUE-13 serving resilience ----------------------------
        # default request deadline (0/None = none), hysteresis shed
        # policy, crash-safe request journal, watchdog escalation
        # (serve default: stall -> snapshot-then-drain), and the
        # deterministic fault injector (reject_alloc / corrupt_journal
        # need the engine's cooperation; crash/stall/signal fire from
        # the driver's before_tick) — all host-side bookkeeping, so
        # the zero-steady-state-recompile ladder contract is untouched
        self.default_deadline_ms = deadline_ms if deadline_ms \
            is not None else flag_float("APEX_TPU_SERVE_DEADLINE_MS")
        self.shed = shed if shed is not None else ShedPolicy.from_flags()
        self.journal = journal
        self.escalation = escalation
        self.fault = fault
        self._esc_handled = False
        self._drain_reason: Optional[str] = None
        self.spec_disabled = False
        self._deadline_count = 0
        self._shed_count = 0
        self._replayed = 0
        self.restarts = 0
        # set on the first submit carrying a deadline: the per-tick
        # enforcement scan is skipped entirely while no request has one
        self._deadlines_active = False
        # a model that brings its own draft (family 'mla_moe' with its
        # multi-token-prediction module) needs no second model: its
        # prefill and verify programs run the module too, on the hidden
        # states they end in, and a tick is ONE program
        self._mtp = (self.speculate_k > 0 and draft_weights is None
                     and model_cfg.mtp_layers > 0)
        if self._mtp:
            if getattr(weights, "mtp", None) is None \
                    or cache_cfg.num_layers != model_cfg.num_layers + 1:
                raise ValueError(
                    "mtp_layers=1 serves weights that hold the MTP "
                    "module (init_mla_moe_weights(mtp=True)) from a "
                    "cache with a latent layer for it "
                    "(default_cache_config)")
            if self.speculate_k != 1 or self.prefill_chunk > 0 \
                    or self.prefix_share:
                raise ValueError(
                    "the MTP draft proposes one token a tick "
                    "(speculate_k=1) after whole-prompt prefills: "
                    "chunked prefill and prefix sharing do not feed the "
                    "module yet")
        elif self.speculate_k > 0 and draft_weights is None:
            raise ValueError(
                "speculate_k > 0 needs a draft: pass draft_weights (+ "
                "draft_cfg) — e.g. extract_serving_weights of a "
                "narrower GPT — or serve a model that brings its own "
                "(family 'mla_moe' with mtp_layers=1: its "
                "multi-token-prediction module)")
        self.draft_cfg = draft_cfg
        self.draft_cache_cfg: Optional[KVCacheConfig] = None
        self.draft_cache = None
        if draft_weights is not None:
            if draft_cfg is None:
                raise ValueError("draft_weights without draft_cfg")
            draft_weights, self._held["draft"] = _held_weights(
                draft_weights, draft_cfg)
            # the draft rides the SAME block pool geometry as the
            # target (same block ids, same tables, one manager), so
            # a (block, offset) slot means the same page in both
            # caches and prefix-shared / CoW'd pages mirror for free
            self.draft_cache_cfg = KVCacheConfig(
                num_layers=draft_cfg.num_layers,
                num_heads=draft_cfg.num_kv_heads,
                head_dim=draft_cfg.head_dim,
                num_blocks=cache_cfg.num_blocks,
                block_size=cache_cfg.block_size,
                kv_dtype=cache_cfg.kv_dtype,
                model_dtype=draft_cfg.dtype)
            self.draft_cache = init_cache(self.draft_cache_cfg)
            if device is not None:
                draft_weights = jax.device_put(draft_weights, device)
                self.draft_cache = jax.device_put(self.draft_cache,
                                                  device)
        self.draft_weights = draft_weights
        # degraded mode for the fast path: sustained verify mismatch
        # auto-disables speculation (alarm + gauge, never a crash)
        if spec_governor == "auto":
            self.spec_governor = SpeculationGovernor() \
                if self.speculate_k > 0 else None
        else:
            self.spec_governor = spec_governor
        # --- ISSUE-17 live metrics plane ----------------------------
        # ``slo`` is an SLOTracker ("auto" builds one from the
        # APEX_TPU_SLO_* flags; None when every dimension is off) fed
        # by the metrics layer's lifecycle hooks and evaluated once
        # per tick; burn transitions route through the watchdog's
        # alarm machinery.  ``exporter`` is a monitor.export.
        # MetricsExporter receiving one lock-free published snapshot
        # per tick (registry + /healthz + /varz payloads) — all host
        # bookkeeping the engine already holds, no device traffic.
        self.slo = SLOTracker.from_flags() if slo == "auto" else slo
        self.exporter = exporter
        self._slo_defined = False
        # request-lifecycle + gauge telemetry (serving/metrics.py):
        # pure host bookkeeping through the monitor sinks — no device
        # traffic, so the one-fetch-per-tick budget is untouched.
        # ``snapshot`` is an optional metrics.SnapshotTrigger polled
        # at every tick boundary (the --serve driver wires SIGUSR1).
        self.metrics = ServeMetrics(monitor=monitor, clock=clock,
                                    tick_every=tick_every,
                                    slo=self.slo)
        self.snapshot = snapshot
        self.manager = KVCacheManager(cache_cfg,
                                      prefix_sharing=self.prefix_share)
        self.cache = self._fresh_cache()
        self.queue: deque = deque()
        self.active: Dict[Any, Request] = {}
        # admitted requests whose chunked prefill is still running:
        # rid -> _PrefillJob, advanced one chunk per engine tick
        self.prefilling: "Dict[Any, _PrefillJob]" = {}
        self.done: List[Request] = []
        self.steps = 0
        self.prefill_tokens = 0
        self.prefill_chunks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self._warm_admissions = 0
        self._prefix_hit_tokens = 0
        self._run_wall_s = 0.0
        # bounded: a weeks-long serve must not grow host memory per
        # token — percentiles read the most recent window only
        self._latencies: deque = deque(maxlen=_LATENCY_WINDOW)
        self._tick_levels: Optional[Dict[str, Any]] = None
        self._tick_mtp: Dict[str, int] = {}    # a tick's MTP drafts
        # the pooled cache: what a tick's prefill chunk and window
        # closings count (zeroed at each step), and the summary pages
        # of the windows before a prompt's last (the chunk program's
        # prefix capacity)
        self._tick_eva: Counter = Counter()
        self._summary_capacity = (model_cfg.max_seq - 1) \
            // cache_cfg.window * cache_cfg.window_summary_pages \
            if cache_cfg.pooled else 0
        self._done_count = 0
        self._preempted_count = 0
        self._done_tokens = 0
        self.decode_wall_s = 0.0
        self.decode_tokens = 0
        self._decode_exec: Dict[Tuple[int, int], Any] = {}
        self._prefill_exec: Dict[int, Any] = {}
        self._extend_exec: Dict[Tuple[int, int, int], Any] = {}
        self._draft_decode_exec: Dict[Tuple[int, int], Any] = {}
        self._draft_prefill_exec: Dict[int, Any] = {}
        self._draft_extend_exec: Dict[Tuple[int, int, int], Any] = {}
        self._cow_exec: Dict[str, Any] = {}
        self._compiles: Dict[str, int] = {}
        # the attention kernels' plan of each compiled decode program,
        # by its bucket's str((batch, pages)) (serving.model.decode_plans)
        self._decode_plans: Dict[str, dict] = {}
        # running sums of _tick_counts over the decode ticks run while
        # a profiler session or a tracer was recording -- the ticks a
        # device trace holds; filled in place, so a holder sees it grow
        self.tick_sums: Dict[str, int] = {}
        # {window or None: layers of that kind}, for the pages a tick's
        # attention reads (families with windowed layers only)
        self._experts_slots = sum(
            lw.e1.shape[0] for lw in weights.layers
            if getattr(lw, "e1", None) is not None)
        # (an EVA layer's window is aligned and its cache pooled: the
        # pooled cache's own counters are in _tick_counts)
        windows = [s.window for s in model_cfg.layers if s.chunk is None]
        self._layer_windows = {w: windows.count(w) for w in set(windows)} \
            if any(windows) else {}
        self._emit_weights_held()

    # --- events -------------------------------------------------------

    def _event(self, name: str, value=None, **attrs) -> None:
        if self.monitor is not None:
            self.monitor.event("serving", name, value=value,
                               step=self.steps, **attrs)

    # --- the weights the programs read --------------------------------

    def _emit_weights_held(self) -> None:
        """One ``weights_held`` event a tree the engine holds: static
        per taking (construction, each swap), so a fact and not a
        rate."""
        for tree, stats in self._held.items():
            self._event("weights_held", tree=tree, **stats)

    # --- compiled-program cache ---------------------------------------

    def _ctx(self):
        """The engine's parallel serving context, if any — a
        TPContext or EPContext (mutually exclusive); both expose the
        same init_cache/shard_weights/jit_* surface."""
        return self.tp if self.tp is not None else self.ep

    def _fresh_cache(self):
        """A zeroed device cache — TP-sharded under a TPContext (the
        head axis committed to the plan), replicated across the
        expert axis under an EPContext, pinned to the replica's
        device when one was given, default placement otherwise.  Used
        at construction and by :meth:`swap_weights` (new weights mean
        every cached k/v row is stale)."""
        if self._ctx() is not None:
            return self._ctx().init_cache()
        cache = init_cache(self.cache_cfg)
        if self.device is not None:
            cache = jax.device_put(cache, self.device)
        return cache

    def _jit_decode(self, draft: bool = False):
        if self._ctx() is not None and not draft:
            return self._ctx().jit_decode(self.weights)
        cfg = self.draft_cfg if draft else self.model_cfg
        ccfg = self.draft_cache_cfg if draft else self.cache_cfg

        if ccfg.pooled:
            # the pooled cache's tick takes its seven vectors a row as
            # the rows of ONE array (:data:`_POOLED_TICK_ROWS`): every
            # numpy argument is an upload of its own, ~0.12 ms of the
            # host's time whatever its size and exposed in every tick
            # (the device waits for it), and this kind would make eight
            @functools.partial(jax.jit, donate_argnums=(1,))
            def step(weights, cache, rows, block_tables):
                (tokens, positions, seq_lens, write_blocks, write_offsets,
                 pool_blocks, pool_offsets) = rows
                return gpt_decode_step(weights, cfg, ccfg, cache, tokens,
                                       positions, block_tables, seq_lens,
                                       write_blocks, write_offsets,
                                       pool_blocks, pool_offsets)

            return step

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(weights, cache, tokens, positions, block_tables,
                 seq_lens, write_blocks, write_offsets):
            return gpt_decode_step(weights, cfg, ccfg, cache, tokens,
                                   positions, block_tables, seq_lens,
                                   write_blocks, write_offsets)

        return step

    def _jit_prefill(self, draft: bool = False):
        if self._ctx() is not None and not draft:
            return self._ctx().jit_prefill(self.weights)
        cfg = self.draft_cfg if draft else self.model_cfg
        ccfg = self.draft_cache_cfg if draft else self.cache_cfg

        prefill = mtp_prefill_step if self._mtp else gpt_prefill_step

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(weights, cache, tokens, length, blocks, *chunk):
            return prefill(weights, cfg, ccfg, cache, tokens, length,
                           blocks, *chunk)

        return step

    def _jit_extend(self, draft: bool = False):
        if self._ctx() is not None and not draft:
            return self._ctx().jit_extend(self.weights)
        cfg = self.draft_cfg if draft else self.model_cfg
        ccfg = self.draft_cache_cfg if draft else self.cache_cfg

        extend = mtp_extend_step if self._mtp else gpt_extend_step

        @functools.partial(jax.jit, donate_argnums=(1,))
        def step(weights, cache, tokens, block_tables, seq_lens,
                 write_blocks, write_offsets):
            return extend(weights, cfg, ccfg, cache, tokens, block_tables,
                          seq_lens, write_blocks, write_offsets)

        return step

    def _jit_cow(self):
        return functools.partial(jax.jit, donate_argnums=(0,))(
            copy_cache_block)

    def _wc(self, draft: bool):
        return (self.draft_weights, self.draft_cache) if draft \
            else (self.weights, self.cache)

    def _decode_args(self, bb: int, pb: int, draft: bool = False):
        z = jnp.zeros((bb,), jnp.int32)
        w, c = self._wc(draft)
        bt = jnp.zeros((bb, pb), jnp.int32)
        if self.cache_cfg.pooled:
            return (w, c, jnp.zeros((_POOLED_TICK_ROWS, bb), jnp.int32), bt)
        return (w, c, z, z, bt, z, z, z)

    def _prefill_args(self, s_pad: int, draft: bool = False):
        w, c = self._wc(draft)
        bs = self.cache_cfg.block_size
        # the pooled cache: one window-aligned chunk (start, the summary
        # pages before it, those its own pages pool to)
        chunk = (jnp.int32(0),
                 jnp.zeros((self._summary_capacity,), jnp.int32),
                 jnp.zeros((s_pad // bs ** 2,), jnp.int32)) \
            if self.cache_cfg.pooled else ()
        return (w, c, jnp.zeros((s_pad,), jnp.int32), jnp.int32(1),
                jnp.zeros((s_pad // bs,), jnp.int32), *chunk)

    def _extend_args(self, bb: int, t: int, pb: int,
                     draft: bool = False):
        w, c = self._wc(draft)
        return (w, c, jnp.zeros((bb, t), jnp.int32),
                jnp.zeros((bb, pb), jnp.int32),
                jnp.zeros((bb,), jnp.int32),
                jnp.zeros((bb, t), jnp.int32),
                jnp.zeros((bb, t), jnp.int32))

    def _compiled(self, cache: dict, key, jit_builder, make_args, label,
                  plans=None):
        """The executable of one bucket, compiled on first use from the
        example arguments ``make_args()`` builds: built only then, since
        they are device arrays and a cached bucket is asked for on
        every tick.  ``plans()``: the program's kernel plans, recorded
        with it (the ``serve_compile`` event, ``router_snapshot()``)."""
        ex = cache.get(key)
        if ex is None:
            t0 = self._clock()
            ex = jit_builder().lower(*make_args()).compile()
            cache[key] = ex
            self._compiles[f"{label}:{key}"] = \
                self._compiles.get(f"{label}:{key}", 0) + 1
            attrs = {}
            if plans is not None:
                attrs["plans"] = self._decode_plans[str(key)] = plans()
            self._event("serve_compile", value=round(
                (self._clock() - t0) * 1e3, 2), what=label,
                bucket=str(key), **attrs)
            # compilation is progress: feed the stall heartbeat so a
            # multi-second AOT warmup cannot trip the watchdog (and,
            # under the serve escalation policy, drain the serve)
            # before the first tick ever runs
            wd = getattr(self.monitor, "watchdog", None)
            if wd is not None:
                wd.observe_step(self.steps)
        return ex

    def _decode_fn(self, bb: int, pb: int):
        return self._compiled(
            self._decode_exec, (bb, pb), self._jit_decode,
            lambda: self._decode_args(bb, pb), "decode",
            lambda: decode_plans(self.model_cfg, self.cache_cfg, bb, pb))

    def _prefill_fn(self, s_pad: int):
        return self._compiled(self._prefill_exec, s_pad,
                              self._jit_prefill,
                              lambda: self._prefill_args(s_pad), "prefill")

    def _extend_fn(self, bb: int, t: int, pb: int):
        return self._compiled(self._extend_exec, (bb, t, pb),
                              self._jit_extend,
                              lambda: self._extend_args(bb, t, pb),
                              "extend")

    def _draft_decode_fn(self, bb: int, pb: int):
        return self._compiled(
            self._draft_decode_exec, (bb, pb),
            functools.partial(self._jit_decode, True),
            lambda: self._decode_args(bb, pb, draft=True), "draft_decode")

    def _draft_prefill_fn(self, s_pad: int):
        return self._compiled(
            self._draft_prefill_exec, s_pad,
            functools.partial(self._jit_prefill, True),
            lambda: self._prefill_args(s_pad, draft=True),
            "draft_prefill")

    def _draft_extend_fn(self, bb: int, t: int, pb: int):
        return self._compiled(
            self._draft_extend_exec, (bb, t, pb),
            functools.partial(self._jit_extend, True),
            lambda: self._extend_args(bb, t, pb, draft=True),
            "draft_extend")

    def _cow_fn(self, which: str):
        cache = self.draft_cache if which == "draft" else self.cache
        return self._compiled(
            self._cow_exec, which, self._jit_cow,
            lambda: (cache, jnp.int32(0), jnp.int32(0)), "cow")

    @property
    def _chunking(self) -> bool:
        return self.prefill_chunk > 0

    def warmup(self) -> Dict[str, float]:
        """AOT-compile every ladder bucket BEFORE traffic, so a
        sanitized serve charges every compile to warmup and the
        steady state compiles exactly once per bucket, ever — across
        every enabled path: whole-prompt prefill (one program per page
        rung; skipped when chunked prefill replaces it), chunk/extend
        programs per (chunk rung x page rung) when chunked prefill or
        prefix sharing can route through them, decode per
        (batch x pages), and with speculation the draft's mirror
        programs plus the (batch x K+1 x pages) verify ladder and the
        copy-on-write block-copy program per cache.  Returns
        ``{bucket label: compile count}`` (all 1 after a fresh
        warmup)."""
        bs = self.cache_cfg.block_size
        spec = self.speculate_k > 0
        draft = spec and not self._mtp    # a second model's mirror programs
        if self.cache_cfg.pooled:
            # a chunk of a prompt is a prefill of its own window
            for ct in self.ladder.chunk_rungs(bs):
                self._prefill_fn(ct)
        elif not self._chunking:
            for pb in self.ladder.pages:
                self._prefill_fn(pb * bs)
                if draft:
                    self._draft_prefill_fn(pb * bs)
        if (self._chunking or self.prefix_share) \
                and not self.cache_cfg.pooled:
            for ct in self.ladder.chunk_rungs(bs):
                for pb in self.ladder.pages:
                    self._extend_fn(1, ct, pb)
                    if draft:
                        self._draft_extend_fn(1, ct, pb)
        for bb in self.ladder.batch:
            for pb in self.ladder.pages:
                self._decode_fn(bb, pb)
                if draft:
                    self._draft_decode_fn(bb, pb)
                if spec:
                    self._extend_fn(bb, self.speculate_k + 1, pb)
        if self.prefix_share:
            self._cow_fn("target")
            if self.draft_cache is not None:
                self._cow_fn("draft")
        return dict(self._compiles)

    # --- request lifecycle --------------------------------------------

    def _reject(self, request: Request, reason: str,
                msg: str) -> None:
        """Refuse a submit: counted + emitted (``request_rejected``,
        the summary's ``requests_rejected`` reasons) before the raise,
        so a caller swallowing the ValueError still leaves an audit
        trail."""
        self.metrics.on_reject(request.rid, reason, self.steps)
        raise ValueError(msg)

    def submit(self, request: Request) -> None:
        if len(request.prompt) < 1:
            self._reject(request, "empty_prompt",
                         f"request {request.rid!r}: empty prompt")
        if request.max_new_tokens < 1:
            # prefill always emits one token, and a negative budget
            # would undercount the reservation can_admit sizes —
            # admission could then exhaust the pool mid-decode
            self._reject(
                request, "max_new_tokens",
                f"request {request.rid!r}: max_new_tokens "
                f"{request.max_new_tokens} < 1")
        worst = len(request.prompt) + request.max_new_tokens
        columns = self.cache_cfg.table_pages(worst)
        if columns > self.ladder.max_pages:
            self._reject(
                request, "ladder_span",
                f"request {request.rid!r}: prompt + max_new_tokens = "
                f"{worst} takes {columns} block-table columns, the "
                f"ladder's span is {self.ladder.max_pages}")
        if worst > self.model_cfg.max_seq:
            self._reject(
                request, "max_seq",
                f"request {request.rid!r}: {worst} tokens exceed the "
                f"model's max_seq {self.model_cfg.max_seq}")
        if request.deadline_ms is None and self.default_deadline_ms \
                and self.default_deadline_ms > 0:
            request.deadline_ms = float(self.default_deadline_ms)
        if request.deadline_ms:
            self._deadlines_active = True
        if request.submit_t is None:
            # a PRE-anchored submit instant is respected: the fleet
            # router stamps a disaggregated request when IT accepted
            # the submission, so queue-wait/TTFT/deadline all count
            # the prefill-replica probe and the KV handoff — the
            # clock must not restart at the decode-side submit
            request.submit_t = self._clock()
        self.queue.append(request)
        self.metrics.on_submit(request, self.steps)
        if self.journal is not None:
            self.journal.record_submit(request, self.steps)

    def resubmit(self, request: Request) -> None:
        """Re-enter a journal-replayed request (crash recovery) WITHOUT
        a second ``request_submitted`` lifecycle event: the chain the
        pre-crash submit opened stays open, its admission/first-token
        stamps are reset for the fresh incarnation, and the terminal
        event still fires exactly once — so ``trace_check --serve``'s
        N submitted ⇒ N terminal holds across the crash.  A replay in
        a fresh process (no open chain for the rid) opens one."""
        if request.deadline_ms:
            self._deadlines_active = True
        tr = self.metrics.reopen(str(request.rid))
        if tr is not None:
            # deadline stays anchored at the ORIGINAL submit: crash
            # downtime counts against the request's SLO, not for it
            request.submit_t = tr.submit_t
        else:
            request.submit_t = self._clock()
            self.metrics.on_submit(request, self.steps)
        self.queue.append(request)
        self._replayed += 1
        self._event("request_replayed", rid=str(request.rid),
                    prompt_len=len(request.prompt),
                    max_new_tokens=request.max_new_tokens)

    def crash_reset(self) -> Dict[str, int]:
        """Discard the tick loop's request bookkeeping the way a crash
        does, keeping what the supervisor owns: the device cache and
        the prefix-share index.  Every in-flight request's blocks are
        freed — registered prompt pages park in the idle LRU, still
        warm for the journal replay's readmission — and the open
        lifecycle chains stay open (the replayed incarnations close
        them).  Returns the lost-state counts for the replay event."""
        lost = {"active": len(self.active),
                "prefilling": len(self.prefilling),
                "queued": len(self.queue)}
        self.restarts += 1
        for rid in list(self.active):
            self.manager.free(rid)
        for rid in list(self.prefilling):
            self.manager.free(rid)
        self.active.clear()
        self.prefilling.clear()
        self.queue.clear()
        self._drain_reason = None
        # re-arm escalation for the recovered attempt (the training
        # loop's per-attempt escalation.reset() discipline): a stall
        # latched before the crash must not blind the next run, and a
        # NEW alarm there must escalate again
        self._esc_handled = False
        if self.escalation is not None:
            self.escalation.reset()
        self._event("crash_reset", **lost)
        return lost

    def _reserved_blocks(self) -> int:
        """Blocks the free pool already owes to in-flight requests
        (active AND mid-prefill): each may still grow to its worst
        case (prompt + max_new), only the pages it has claimed so far
        left the free list, and a request whose next append will
        copy-on-write a shared page owes one replacement block too."""
        total = 0
        in_flight = list(self.active.items()) \
            + [(rid, job.req) for rid, job in self.prefilling.items()]
        for rid, req in in_flight:
            worst = self.cache_cfg.blocks_for(
                len(req.prompt) + req.max_new_tokens)
            total += max(0, worst - self.manager.held_blocks(rid))
            if self.prefix_share:
                total += self.manager.pending_cow_blocks(rid)
        return total

    def _cow_copy(self, src: int, dst: int) -> None:
        """Device-side copy-on-write of one page, mirrored into the
        draft cache (same block ids by construction)."""
        fn = self._cow_fn("target")
        self.cache = fn(self.cache, jnp.int32(src), jnp.int32(dst))
        if self.draft_cache is not None:
            fnd = self._cow_fn("draft")
            self.draft_cache = fnd(self.draft_cache, jnp.int32(src),
                                   jnp.int32(dst))
        self._event("cow_block", src=int(src), dst=int(dst))

    def _admit(self, req: Request,
               prefix: Optional[PrefixMatch] = None) -> None:
        p_len = len(req.prompt)
        t0 = self._clock()
        if prefix is None:          # step() passes its admission match
            prefix = self.manager.match_prefix(req.prompt)
        # (the pooled cache claims a window at a time: _prefill_step)
        self.manager.alloc(
            req.rid, min(p_len, self.cache_cfg.window or p_len),
            shared_blocks=prefix.blocks)
        if prefix.warm:
            self._warm_admissions += 1
            self._prefix_hit_tokens += prefix.tokens
        if prefix.cow:
            # full-prompt warm hit: the tail (the final token) will be
            # re-written into the last mapped page — copy it private
            # before any write touches it
            cow = self.manager.make_private(req.rid,
                                            len(prefix.blocks) - 1)
            if cow is not None:
                self._cow_copy(*cow)
        req.admitted_at_step = self.steps
        if not prefix.warm and not self._chunking:
            # cold whole-prompt path: one flash-forward prefill (plus
            # the draft's, under speculation) covers the prompt
            bs = self.cache_cfg.block_size
            pages_bucket = self.ladder.pick_pages(
                self.cache_cfg.blocks_for(p_len))
            s_pad = pages_bucket * bs
            bt = self.manager.block_table(req.rid, s_pad // bs)
            tokens = np.zeros(s_pad, np.int32)
            tokens[:p_len] = req.prompt
            with span("apex.serve.prefill"):
                fn = self._prefill_fn(s_pad)
                with span("apex.serve.prefill.dispatch"):
                    self.cache, next_token = fn(
                        self.weights, self.cache, jnp.asarray(tokens),
                        jnp.int32(p_len), jnp.asarray(bt))
                    if self.draft_cache is not None:
                        dfn = self._draft_prefill_fn(s_pad)
                        self.draft_cache, _ = dfn(
                            self.draft_weights, self.draft_cache,
                            jnp.asarray(tokens), jnp.int32(p_len),
                            jnp.asarray(bt))
                with span("apex.serve.prefill.fetch"):
                    # explicit host sync: the admission boundary needs
                    # the token to seed the decode
                    if self._mtp:            # [first token, its draft]
                        first, req.draft = (int(t) for t in
                                            np.asarray(next_token))
                    else:
                        first = int(next_token)
            dt = self._clock() - t0
            req.out_tokens.append(first)
            req.token_latency_s.append(dt)
            self._latencies.append(dt)
            self.active[req.rid] = req
            self.prefill_tokens += p_len
            self.manager.register_prefix(req.rid, req.prompt)
            # request_admitted (queue wait) + request_first_token
            # (TTFT): t0 is the instant queue wait ended
            self.metrics.on_admit(req, self.steps, t0, dt,
                                  prompt_len=p_len, s_pad=s_pad)
            return
        # chunk path: warm tail, and/or chunked prefill.  The job owns
        # its blocks already; k/v streams in via extend-step chunks —
        # one per tick when chunking is on (interleaved with decode,
        # so a long admission cannot monopolize a tick), or drained
        # right here for a warm tail with chunking off.
        job = _PrefillJob(req=req,
                          tokens=np.asarray(req.prompt, np.int32),
                          written=prefix.tokens, start=prefix.tokens,
                          admit_t=t0)
        self.metrics.on_admit(req, self.steps, t0, None,
                              prompt_len=p_len,
                              warm_tokens=prefix.tokens)
        if self._chunking:
            self.prefilling[req.rid] = job
            return
        while not self._prefill_step(job):
            pass

    def _prefill_step(self, job: _PrefillJob) -> bool:
        """Write one prefill chunk of ``job``'s prompt (valid tokens
        back-aligned in the chunk bucket, front padding writing to the
        dump page); on the chunk that completes the prompt, fetch the
        first generated token and move the request into the decode
        set.  Returns True when the prefill finished."""
        req = job.req
        p_len = len(job.tokens)
        bs = self.cache_cfg.block_size
        rem = p_len - job.written
        ct = self.ladder.pick_chunk(rem, bs)
        n = min(rem, ct)
        if self.cache_cfg.pooled:
            return self._prefill_window(job, ct, n)
        pb = self.ladder.pick_pages(self.manager.num_pages(req.rid))
        bt = self.manager.block_table(req.rid, pb)
        table = self.manager.blocks(req.rid)
        toks = np.zeros(ct, np.int32)
        wb = np.full(ct, DUMP_BLOCK, np.int32)
        wo = np.zeros(ct, np.int32)
        toks[ct - n:] = job.tokens[job.written:job.written + n]
        for j in range(n):
            p = job.written + j
            wb[ct - n + j] = table[p // bs]
            wo[ct - n + j] = p % bs
        sl = np.asarray([job.written + n], np.int32)
        t0 = self._clock()
        with span("apex.serve.prefill"):
            fn = self._extend_fn(1, ct, pb)
            with span("apex.serve.prefill.dispatch"):
                self.cache, out = fn(
                    self.weights, self.cache, jnp.asarray(toks[None]),
                    jnp.asarray(bt[None]), jnp.asarray(sl),
                    jnp.asarray(wb[None]), jnp.asarray(wo[None]))
                if self.draft_cache is not None:
                    dfn = self._draft_extend_fn(1, ct, pb)
                    self.draft_cache, _ = dfn(
                        self.draft_weights, self.draft_cache,
                        jnp.asarray(toks[None]), jnp.asarray(bt[None]),
                        jnp.asarray(sl), jnp.asarray(wb[None]),
                        jnp.asarray(wo[None]))
            job.written += n
            self.prefill_chunks += 1
            first = None
            if job.written >= p_len:
                # the only host sync: non-final chunks stay async
                with span("apex.serve.prefill.fetch"):
                    first = int(np.asarray(out)[0, -1])
        return self._chunk_done(job, n, first, self._clock() - t0)

    def _prefill_window(self, job: _PrefillJob, ct: int, n: int) -> bool:
        """:meth:`_prefill_step` on the pooled cache: the next ``n``
        positions are (the head of) a window of their own, prefilled as
        one right-padded prompt on the ``ct`` rung.  The window's pages
        are claimed now and, where the chunk fills it, given back once
        the program that pools them has been handed to the device."""
        cfg, mgr, rid = self.cache_cfg, self.manager, job.req.rid
        bs, start = cfg.block_size, job.written
        mgr.grow_to(rid, start + n)
        toks = np.zeros(ct, np.int32)
        toks[:n] = job.tokens[start:start + n]
        blocks = np.full(ct // bs, DUMP_BLOCK, np.int32)
        own = mgr.blocks(rid)
        blocks[:len(own)] = own
        summaries = mgr.summary_blocks(rid)
        closed = start // cfg.window * cfg.window_summary_pages
        table = np.full(self._summary_capacity, DUMP_BLOCK, np.int32)
        table[:closed] = summaries[:closed]
        pool = np.full(ct // bs ** 2, DUMP_BLOCK, np.int32)
        pool[:len(summaries) - closed] = summaries[closed:]
        t0 = self._clock()
        with span("apex.serve.prefill"):
            fn = self._prefill_fn(ct)
            with span("apex.serve.prefill.dispatch"):
                self.cache, out = fn(
                    self.weights, self.cache, toks, np.int32(n), blocks,
                    np.int32(start), table, pool)
            freed = len(mgr.close_window(rid))
            job.written += n
            self.prefill_chunks += 1
            first = None
            if job.written >= len(job.tokens):
                with span("apex.serve.prefill.fetch"):
                    first = int(out)             # the only host sync
        rows = start // bs                  # pooled rows before the chunk
        self._tick_eva.update(
            eva_chunks=1, eva_chunk_tokens=n, eva_chunk_summary_rows=rows,
            eva_chunk_pairs=n * (n + 1) // 2 + n * rows,
            eva_pages_freed=freed, eva_windows_closed=int(freed > 0))
        return self._chunk_done(job, n, first, self._clock() - t0)

    def _chunk_done(self, job: _PrefillJob, n: int, first: Optional[int],
                    dt: float) -> bool:
        """What follows a prefill chunk of ``n`` tokens that took
        ``dt``: its event and, where it completed the prompt (``first``
        is the first generated token), the request's move into the
        decode set.  True when the prefill finished."""
        req, p_len = job.req, len(job.tokens)
        done = first is not None
        self._event("prefill_chunk", value=round(dt * 1e3, 3),
                    rid=str(req.rid), tokens=int(n),
                    written=int(job.written), prompt_len=p_len)
        if done:
            req.out_tokens.append(first)
            req.token_latency_s.append(dt)
            self._latencies.append(dt)
            self.active[req.rid] = req
            self.prefill_tokens += p_len - job.start
            self.manager.register_prefix(req.rid, job.tokens)
            self.metrics.on_first_token(req, self.steps,
                                        self._clock())
        return done

    def _terminate(self, req: Request, terminal: str, *,
                   where: str = "queued") -> None:
        """The ONE terminal transition: free owned blocks (``where`` in
        active/prefilling; queued requests own none), move the request
        into ``done``, bump the per-reason counter, emit the terminal
        ``request_done`` lifecycle event, and journal it — every
        terminal path (finished, drain-preempted, deadline, shed) goes
        through here, so none can skip the accounting."""
        req.terminal = terminal
        if where == "active":
            self.manager.free(req.rid)
            del self.active[req.rid]
        elif where == "prefilling":
            self.manager.free(req.rid)
            del self.prefilling[req.rid]
        self.done.append(req)
        if terminal == "finished":
            self._done_count += 1
        elif terminal == "preempted":
            self._preempted_count += 1
        elif terminal == "shed":
            self._shed_count += 1
        else:                       # deadline / deadline_exceeded
            self._deadline_count += 1
        self._done_tokens += len(req.out_tokens)
        # terminal lifecycle event (request_done) with the full
        # queued/prefill/decode breakdown
        self.metrics.on_done(req, self.steps)
        if self.journal is not None:
            self.journal.record_terminal(req, self.steps)

    def _finish(self, req: Request) -> None:
        self._terminate(req, "preempted" if req.preempted
                        else "finished", where="active")

    def _terminating(self) -> bool:
        return (self.autoresume is not None
                and self.autoresume.termination_requested())

    # --- deadlines, shedding, escalation (ISSUE-13) -------------------

    def _past_deadline(self, req: Request, now: float) -> bool:
        if req.deadline_ms is None or req.deadline_ms <= 0 \
                or req.submit_t is None:
            return False
        return (now - req.submit_t) * 1e3 >= req.deadline_ms

    def _expire_deadlines(self) -> None:
        """Tick-boundary deadline enforcement.  Runs at the START of a
        tick, so a deadline crossed during tick K's decode is noticed
        at the K+1 boundary — AFTER tick K's tokens were delivered
        (the deadline-at-boundary semantics the tests pin: expiry
        exactly on a boundary never claws back a delivered token)."""
        if not self._deadlines_active:
            return
        now = self._clock()
        if self.queue:
            keep: deque = deque()
            while self.queue:
                q = self.queue.popleft()
                if self._past_deadline(q, now):
                    self._event("deadline_exceeded", rid=str(q.rid),
                                where="queued",
                                deadline_ms=q.deadline_ms)
                    self._terminate(q, "deadline_exceeded")
                else:
                    keep.append(q)
            self.queue = keep
        for rid in [r for r, q in list(self.active.items())
                    if self._past_deadline(q, now)]:
            q = self.active[rid]
            self._event("deadline_exceeded", rid=str(rid),
                        where="active", deadline_ms=q.deadline_ms,
                        tokens=len(q.out_tokens))
            self._terminate(q, "deadline", where="active")
        for rid in [r for r, j in list(self.prefilling.items())
                    if self._past_deadline(j.req, now)]:
            q = self.prefilling[rid].req
            self._event("deadline_exceeded", rid=str(rid),
                        where="prefilling", deadline_ms=q.deadline_ms)
            self._terminate(q, "deadline", where="prefilling")

    def _load(self) -> Tuple[float, int]:
        """(pool pressure, admission backlog) for the shed policy.
        Pool pressure counts only what an allocation could NOT draw on
        — idle shared pages are reclaimable, so they are headroom, not
        pressure."""
        usable = max(1, self.cache_cfg.usable_blocks)
        frac = 1.0 - self.manager.available_blocks / usable
        return frac, len(self.queue) + len(self.prefilling)

    def _shed_victim(self, *, from_pool: bool):
        """The next victim under pressure: lowest priority first, then
        shortest progress.  Queue pressure sheds BACKLOG only — queued
        work (zero sunk cost) before mid-prefill jobs, never a running
        decode, which costs paid-for progress without moving the
        backlog signal at all.  Pool pressure must shed block OWNERS —
        mid-prefill jobs (no tokens yet) before running requests,
        fewest generated tokens first."""
        if not from_pool:
            if self.queue:
                # newest submission at equal priority: the latest
                # arrival has waited least
                victim = min(
                    enumerate(self.queue),
                    key=lambda iq: (iq[1].priority, -iq[0]))
                del self.queue[victim[0]]
                return "queued", victim[1]
            if self.prefilling:
                rid = min(self.prefilling,
                          key=lambda r: (
                              self.prefilling[r].req.priority,
                              self.prefilling[r].written
                              - self.prefilling[r].start))
                return "prefilling", self.prefilling[rid].req
            return None
        # progress = prefill chunks written for a mid-prefill job,
        # generated tokens for a running one — least paid-for work
        # dies first
        owners = [("prefilling", j.req, j.written - j.start)
                  for j in self.prefilling.values()] \
            + [("active", q, len(q.out_tokens))
               for q in self.active.values()]
        if not owners:
            return None
        where, req, _ = min(owners,
                            key=lambda w: (w[1].priority, w[2]))
        return where, req

    def _apply_shedding(self) -> bool:
        """Advance the shed policy's hysteresis state and, while
        engaged, shed lowest-priority / shortest-progress work until
        the load drops below the LOW-water marks.  Returns whether
        shedding is engaged (the engine admits nothing while it is —
        the no-flap half of the hysteresis contract)."""
        if self.shed is None or not self.shed.enabled:
            return False
        pf, qd = self._load()
        if not self.shed.update(pool_frac=pf, queue_depth=qd):
            return False
        while True:
            pf, qd = self._load()
            if not self.shed.over_low(pf, qd):
                break
            over_queue = self.shed.queue_hw > 0 \
                and qd > self.shed.queue_lw
            victim = self._shed_victim(from_pool=not over_queue)
            if victim is None:
                break
            where, req = victim
            self._event("request_shed", rid=str(req.rid), where=where,
                        priority=req.priority,
                        tokens=len(req.out_tokens),
                        pool_frac=round(pf, 4), queue_depth=qd)
            self._terminate(req, "shed", where=where)
        # shedding may have dropped the load through the band already
        pf, qd = self._load()
        self.shed.update(pool_frac=pf, queue_depth=qd)
        return self.shed.engaged

    def _poll_escalation(self) -> None:
        """Tick-boundary escalation poll: a watchdog alarm the serve
        policy maps to ``snapshot_then_drain`` (the serve default for
        ``stall`` — never ``ignore`` a wedged decode) dumps ONE
        structured engine snapshot and latches a drain for the next
        boundary; ``abort`` actions raise
        :class:`~apex_tpu.resilience.EscalationAbort` for the
        supervisor (:func:`~.resilience.run_serving`) to restart."""
        if self.escalation is None or self._esc_handled:
            return
        esc = self.escalation.pending()
        if esc is None:
            return
        self._esc_handled = True
        from ..resilience import SNAPSHOT_THEN_DRAIN, EscalationAbort

        if esc.action == SNAPSHOT_THEN_DRAIN:
            if self.monitor is not None:
                self.monitor.event(
                    "serving", "engine_snapshot", step=self.steps,
                    reason=f"escalation:{esc.alarm}",
                    **self.snapshot_state())
            self._event("escalation_drain", alarm=esc.alarm,
                        action=esc.action)
            self._drain_reason = f"escalation:{esc.alarm}"
            return
        raise EscalationAbort(esc.alarm, esc.action, step=self.steps)

    def _drain(self, source: str) -> None:
        """Stop serving NOW, accounting for every request: in-flight
        generation abandoned cleanly (blocks freed), mid-prefill jobs
        dropped (no first token — the whole post-admission wall reads
        as prefill), queued-never-admitted requests closed with
        queue-wait-only chains.  Every submitted request ends terminal
        ``preempted`` — nothing vanishes.  A request that already
        emitted its full token budget is evicted as ``finished``
        first: completing during the very tick that latched the drain
        must not read back as preemption."""
        for rid in [r for r, q in self.active.items() if q.done]:
            self._finish(self.active[rid])
        for rid in list(self.active):
            q = self.active[rid]
            q.preempted = True
            self._terminate(q, "preempted", where="active")
        for rid in list(self.prefilling):
            q = self.prefilling[rid].req
            q.preempted = True
            self._terminate(q, "preempted", where="prefilling")
        while self.queue:
            q = self.queue.popleft()
            q.preempted = True
            self._terminate(q, "preempted")
        self._drain_reason = None
        self._event("serve_preempt", source=source)

    # --- the engine tick ----------------------------------------------

    def step(self) -> int:
        """One continuous-batching tick: poll the escalation policy,
        enforce deadlines (boundary semantics: after the previous
        tick's tokens were delivered), evict finished, apply the shed
        policy, advance ONE pending prefill chunk (chunked prefill
        interleaves admission cost with decode — a long prompt never
        monopolizes a tick), admit (unless draining, shedding, or a
        ``reject_alloc`` fault simulates pool exhaustion), run one
        bucketed decode step — speculative when ``speculate_k > 0`` —
        over every active request.  Returns the number of tokens
        generated this tick."""
        # Python's collections get a span while something records
        watch_collector(recording())
        with span("apex.serve.step") as tick:
            self._tick_levels = None
            self._tick_mtp = {}
            self._tick_eva = Counter()
            gained, admitted = self._tick()
            if self._tick_eva and recording():
                # what the tick's prefill chunk and window closings
                # counted (the pooled cache), decode tick or none
                for key, value in self._tick_eva.items():
                    self.tick_sums[key] = self.tick_sums.get(key, 0) + value
            if self._tick_levels is not None and recording():
                # the tick's counters ride on its span: an operator
                # reads them in xprof on the step they belong to
                tick.set(admitted=admitted, **self._tick_mtp,
                         **self._tick_eva,
                         **{k: self._tick_levels[k]
                            for k in _STEP_SPAN_COUNTERS})
            return gained

    def _tick(self) -> Tuple[int, int]:
        """The body of :meth:`step` under its span: ``(tokens
        generated, requests admitted)``."""
        with span("apex.serve.schedule"):
            self._poll_escalation()
            # finished requests leave BEFORE deadline enforcement: a
            # request whose last token arrived within its deadline must
            # end terminal "finished" even when the next boundary lands
            # past the deadline
            for rid in [r for r, q in self.active.items() if q.done]:
                self._finish(self.active[rid])
            self._expire_deadlines()
            shedding = self._apply_shedding()
        advanced_prefill = False
        if self.prefilling:
            # FIFO: the oldest admission's next chunk, exactly one
            # per tick
            rid = next(iter(self.prefilling))
            if self._prefill_step(self.prefilling[rid]):
                del self.prefilling[rid]
            advanced_prefill = True
        admit = (not self._terminating()
                 and self._drain_reason is None and not shedding)
        if admit and self.queue and self.fault is not None \
                and self.fault.reject_alloc(self.steps):
            # simulated pool exhaustion: this tick admits nothing,
            # exactly once per armed spec (the serve fault drill).
            # Polled only when work is actually queued, so a spec
            # landing on an idle tick defers to one it can affect.
            self._event("alloc_rejected", injected=True)
            admit = False
        admitted = 0
        if admit and self.queue:
            with span("apex.serve.admit"):
                while (self.queue
                       and (len(self.active) + len(self.prefilling)
                            < self.ladder.max_batch)):
                    # one match per admission attempt: the PrefixMatch
                    # feeds both the reservation check and the
                    # admission itself (hashing the prompt every tick
                    # for a blocked queue head would sit on the hot
                    # path for nothing)
                    req = self.queue[0]
                    prefix = self.manager.match_prefix(req.prompt)
                    if not self.manager.can_admit(
                            len(req.prompt), req.max_new_tokens,
                            reserved_blocks=self._reserved_blocks(),
                            prefix=prefix):
                        break
                    self._admit(self.queue.popleft(), prefix=prefix)
                    admitted += 1
        # requests may finish at admission (max_new_tokens == 1)
        for rid in [r for r, q in self.active.items() if q.done]:
            self._finish(self.active[rid])
        if not self.active:
            if advanced_prefill:
                # a pure-prefill tick still crosses the telemetry
                # boundary: gauges, snapshot poll, and the watchdog
                # stall heartbeat must see chunked-prefill progress
                # even before anything decodes
                self._tick_tail(0, 0, 0)
            return 0, admitted
        reqs = [self.active[r] for r in sorted(self.active,
                                               key=lambda r: str(r))]
        if self.speculate_k > 0:
            return self._spec_tick(reqs), admitted
        return self._decode_tick(reqs), admitted

    def _append_slot(self, req: Request):
        """One KV append with the copy-on-write guard: a slot landing
        in a shared page (the owner's registered partial prompt
        block) copies the page private first — append never mutates
        a shared page."""
        if self.prefix_share:
            cow = self.manager.cow_for_append(req.rid)
            if cow is not None:
                self._cow_copy(*cow)
        return self.manager.append(req.rid)

    def _decode_tick(self, reqs: List[Request]) -> int:
        with span("apex.serve.decode.build"):
            n = len(reqs)
            bb = self.ladder.pick_batch(n)
            slots = [self._append_slot(q) for q in reqs]
            pb = self.ladder.pick_pages(
                max(self.manager.num_pages(q.rid) for q in reqs))
            pooled = self.cache_cfg.pooled
            # the vectors a row are views of one array: the pooled
            # cache's tick uploads it whole (``_jit_decode``), with where
            # a row's completed page pools to as its last two rows
            rows = np.zeros((_POOLED_TICK_ROWS, bb), np.int32)
            tokens, positions, seq_lens, wb, wo, pool_b, pool_o = rows
            wb[:] = pool_b[:] = DUMP_BLOCK
            bt = np.full((bb, pb), DUMP_BLOCK, np.int32)
            for i, (q, (blk, off)) in enumerate(zip(reqs, slots)):
                new_len = self.manager.seq_len(q.rid)   # post-append
                tokens[i] = q.out_tokens[-1]
                positions[i] = new_len - 1
                seq_lens[i] = new_len
                wb[i], wo[i] = blk, off
                bt[i] = self.manager.block_table(q.rid, pb)
                if pooled:
                    pool_b[i], pool_o[i] = self.manager.pool_slot(q.rid)
            fn = self._decode_fn(bb, pb)
        t0 = self._clock()
        with span("apex.serve.decode.dispatch"):
            # the executable uploads its numpy inputs itself, in one
            # batch: six jnp.asarray calls before it cost 1 ms a tick
            self.cache, next_tokens = fn(
                self.weights, self.cache,
                *((rows, bt) if pooled
                  else (tokens, positions, bt, seq_lens, wb, wo)))
            if pooled:
                # a window whose last position this step wrote (and
                # pooled) is closed: its pages go back now that the step
                # is on its way, and not before -- another row of this
                # step must not be handed a page this one still reads
                for q in reqs:
                    freed = len(self.manager.close_window(q.rid))
                    if freed:
                        self._tick_eva.update(eva_pages_freed=freed,
                                              eva_windows_closed=1)
        with span("apex.serve.decode.fetch"):
            out = np.asarray(next_tokens)    # the tick's ONE device fetch
        dt = self._clock() - t0
        with span("apex.serve.deliver"):
            for i, q in enumerate(reqs):
                q.out_tokens.append(int(out[i]))
                q.token_latency_s.append(dt)
                self._latencies.append(dt)
            self.decode_wall_s += dt
            self.decode_tokens += n
            self.steps += 1
            counts = self._tick_counts(n, seq_lens, out[bb:], bb * pb)
            self._event("decode_step", value=round(dt * 1e3, 3),
                        batch=n, batch_bucket=bb, pages_bucket=pb,
                        **counts)
        self._tick_tail(n, bb, pb)
        return n

    def _tick_counts(self, n: int, seq_lens: np.ndarray,
                     extra: np.ndarray, slots: int = 0) -> Dict[str, int]:
        """What a decode tick of a family with routed experts or
        windowed layers counts, for the ``decode_step`` event and,
        while :func:`~..monitor.tracing.recording`, added into
        :attr:`tick_sums`; empty for GPT-2 and where neither a monitor
        nor a recorder looks.  ``extra`` is what the step appended to
        the tick's tokens (:data:`~.model.MOE_TICK_COUNTERS`, as many
        of them as the step's layers count).  The pages are the host's
        own bookkeeping, summed over the layers of each kind:
        ``pages_full`` / ``tokens_full`` the live pages and positions
        the full layers read, ``pages_window`` / ``tokens_window``
        those the windowed layers read, ``pages_dead`` those the
        windowed layers hold wholly behind their window -- cache that
        a block pool of their own would have freed."""
        record = recording()
        if not record and self.monitor is None:
            return {}
        counts = dict(zip(MOE_TICK_COUNTERS, (int(v) for v in extra)))
        if self._layer_windows:
            bs = self.cache_cfg.block_size
            lens = seq_lens[:n].astype(np.int64)
            pages = -(-lens // bs)
            counts.update(ticks=1, rows=n, pages_full=0, tokens_full=0,
                          pages_window=0, tokens_window=0, pages_dead=0)
            for window, layers in self._layer_windows.items():
                if window is None:
                    counts["pages_full"] += layers * int(pages.sum())
                    counts["tokens_full"] += layers * int(lens.sum())
                    continue
                dead = np.maximum(lens - window, 0) // bs
                counts["pages_dead"] += layers * int(dead.sum())
                counts["pages_window"] += layers * int(
                    (pages - dead).sum())
                counts["tokens_window"] += layers * int(
                    np.minimum(lens, window).sum())
        if self.cache_cfg.latent:
            # a latent layer's row is read whole by every head:
            # ``latent_pages`` / ``latent_tokens`` the live pages and
            # positions, summed over the model's latent layers;
            # ``experts_slots`` the experts held here, summed over the
            # MoE layers (what ``experts_hit`` is a share of)
            lens = seq_lens[:n].astype(np.int64)
            layers = self.model_cfg.num_layers
            counts.update(
                ticks=1, rows=n,
                latent_pages=layers * int(
                    (-(-lens // self.cache_cfg.block_size)).sum()),
                latent_tokens=layers * int(lens.sum()),
                experts_slots=self._experts_slots)
        eva = {}
        if self.cache_cfg.pooled:
            # EVA layers read, a live row and layer, the exact rows of
            # the row's own window up to its position
            # (``eva_window_rows``) and one pooled row a page of every
            # closed window (``eva_summary_rows``), both summed over the
            # layers, ``eva_rows`` the two together; ``eva_pages_live``
            # the pages of both lists that hold them, of the
            # ``eva_pages_slots`` (batch rung x page rung) the kernel's
            # grid was launched over.  They join what the tick's prefill
            # chunk and window closings counted (``_tick_eva``), which
            # ``step`` sums and puts on its span: a tick that only
            # prefills counts too.
            cfg, layers = self.cache_cfg, self.model_cfg.num_layers
            at = seq_lens[:n].astype(np.int64) - 1
            closed, rows = at // cfg.window, at % cfg.window + 1
            window_rows = layers * int(rows.sum())
            summary_rows = layers * cfg.window_pages * int(closed.sum())
            counts.update(ticks=1, rows=n)
            eva = dict(
                eva_window_rows=window_rows, eva_summary_rows=summary_rows,
                eva_rows=window_rows + summary_rows,
                eva_pages_live=int((closed * cfg.window_summary_pages
                                    - (-rows // cfg.block_size)).sum()),
                eva_pages_slots=slots)
            self._tick_eva.update(eva)
        if record:
            for key, value in counts.items():
                self.tick_sums[key] = self.tick_sums.get(key, 0) + value
        return {**counts, **eva}

    def _spec_tick(self, reqs: List[Request]) -> int:
        """One speculative tick: the draft proposes K tokens row by
        row (K small decode dispatches), the target scores all K+1
        positions in ONE multi-token extend call, and greedy-match
        acceptance keeps the longest draft prefix agreeing with the
        target plus one corrected token — so every emitted token is
        exactly what non-speculative greedy decode would have
        produced, and a tick advances each row by 1..K+1 tokens.
        Rejected positions roll the KV write cursor back through the
        manager's (block, offset) slot accounting; the draft cache
        catches up its one unwritten position on full acceptance so
        the next tick's proposals stay on-policy."""
        with span("apex.serve.decode.build"):
            K = self.speculate_k
            T = K + 1
            n = len(reqs)
            bb = self.ladder.pick_batch(n)
            base = np.zeros(bb, np.int32)
            caps = np.zeros(bb, np.int32)
            slots: List[List[Tuple[int, int]]] = []
            for i, q in enumerate(reqs):
                base[i] = self.manager.seq_len(q.rid)
                # a row near its token budget writes fewer real slots —
                # the reservation contract (prompt + max_new) caps the
                # pages a tick may claim, so overshoot positions go to
                # the dump page and their (unused) logits are garbage
                caps[i] = max(1, min(T, q.max_new_tokens
                                     - len(q.out_tokens)))
                row = []
                for j in range(int(caps[i])):
                    if j == 0:
                        row.append(self._append_slot(q))
                    else:
                        row.append(self.manager.append(q.rid))
                slots.append(row)
            pb = self.ladder.pick_pages(
                max(self.manager.num_pages(q.rid) for q in reqs))
            bt = np.full((bb, pb), DUMP_BLOCK, np.int32)
            for i, q in enumerate(reqs):
                bt[i] = self.manager.block_table(q.rid, pb)
            bt_j = jnp.asarray(bt)
        t0 = self._clock()
        # --- draft proposals: K sequential single-token steps, or the
        # one token the model's own MTP module proposed when the last
        # tick (or the prefill) ran it
        d = np.zeros((bb, K), np.int32)
        prev = np.zeros(bb, np.int32)
        for i, q in enumerate(reqs):
            prev[i] = q.out_tokens[-1]
            if self._mtp:
                d[i, 0] = q.draft
        for k in (() if self._mtp else range(1, K + 1)):
            with span("apex.serve.decode.build"):
                toks = prev if k == 1 else d[:, k - 2]
                pos = np.zeros(bb, np.int32)
                sl = np.zeros(bb, np.int32)
                wbk = np.full(bb, DUMP_BLOCK, np.int32)
                wok = np.zeros(bb, np.int32)
                for i in range(n):
                    pos[i] = base[i] + k - 1
                    sl[i] = base[i] + k
                    if k - 1 < caps[i]:
                        wbk[i], wok[i] = slots[i][k - 1]
                dfn = self._draft_decode_fn(bb, pb)
            with span("apex.serve.decode.dispatch"):
                self.draft_cache, nt = dfn(
                    self.draft_weights, self.draft_cache,
                    jnp.asarray(toks), jnp.asarray(pos), bt_j,
                    jnp.asarray(sl), jnp.asarray(wbk),
                    jnp.asarray(wok))
            with span("apex.serve.decode.fetch"):
                d[:, k - 1] = np.asarray(nt)
        # --- target verification: ONE teacher-forced extend call ----
        with span("apex.serve.decode.build"):
            vt = np.zeros((bb, T), np.int32)
            wbv = np.full((bb, T), DUMP_BLOCK, np.int32)
            wov = np.zeros((bb, T), np.int32)
            slv = np.zeros(bb, np.int32)
            for i in range(n):
                vt[i, 0] = prev[i]
                vt[i, 1:] = d[i]
                slv[i] = base[i] + T
                for j, (blk, off) in enumerate(slots[i]):
                    wbv[i, j], wov[i, j] = blk, off
            fn = self._extend_fn(bb, T, pb)
        with span("apex.serve.decode.dispatch"):
            self.cache, out = fn(
                self.weights, self.cache, jnp.asarray(vt), bt_j,
                jnp.asarray(slv), jnp.asarray(wbv), jnp.asarray(wov))
        with span("apex.serve.decode.fetch"):
            a = np.asarray(out)          # (bb, T) — the tick's fetch
        # --- greedy-match acceptance + rollback ---------------------
        with span("apex.serve.deliver"):
            gained = 0
            full_rows: List[int] = []
            keeps: List[int] = []
            tick_proposed = 0
            tick_accepted = 0
            for i, q in enumerate(reqs):
                cap = int(caps[i])
                emit = [int(a[i, 0])]
                j = 0
                while j < cap - 1 and int(d[i, j]) == emit[-1]:
                    emit.append(int(a[i, j + 1]))
                    j += 1
                tick_proposed += max(0, cap - 1)
                tick_accepted += j
                if q.eos_token is not None and q.eos_token in emit:
                    emit = emit[:emit.index(q.eos_token) + 1]
                keep = len(emit)
                if keep < cap:
                    self.manager.truncate(q.rid, int(base[i]) + keep)
                if keep == T:
                    full_rows.append(i)
                q.out_tokens.extend(emit)
                if self._mtp:      # the draft of the last slot kept
                    q.draft = int(a[i, T + keep - 1])
                keeps.append(keep)
                gained += keep
            dt = self._clock() - t0
            # amortize the tick wall over each row's gained tokens — the
            # tokens arrive together, so the honest per-token figure is
            # the tick cost split across them (the same population
            # ServeSummary.itl draws from)
            for q, keep in zip(reqs, keeps):
                share = dt / keep
                for _ in range(keep):
                    q.token_latency_s.append(share)
                    self._latencies.append(share)
            self.spec_proposed += tick_proposed
            self.spec_accepted += tick_accepted
            if self._mtp and recording():
                self._tick_mtp = dict(mtp_proposed=tick_proposed,
                                      mtp_accepted=tick_accepted)
                for key, value in self._tick_mtp.items():
                    self.tick_sums[key] = self.tick_sums.get(key, 0) \
                        + value
            self.metrics.gauges.on_spec(tick_proposed, tick_accepted)
            if self.spec_governor is not None \
                    and self.spec_governor.observe(tick_proposed,
                                                   tick_accepted):
                # degraded mode: sustained verify mismatch (a drifted or
                # stalled draft) — turn speculation off for the rest of
                # the run.  Alarm + gauge, never a crash; output identity
                # is preserved (speculative greedy == greedy), so the only
                # observable change is ITL returning to one token/tick.
                self.spec_disabled = True
                self.speculate_k = 0
                if self.monitor is not None:
                    self.monitor.event(
                        "alarm", "spec_disabled", step=self.steps,
                        low_streak=self.spec_governor.window,
                        min_accept=self.spec_governor.min_accept)
        # --- draft catch-up: on full acceptance the draft never wrote
        # position base + K (the target's verify did) — one masked
        # draft step fills it so next tick's proposals read real k/v
        if full_rows and not self._mtp:     # (the module caught up in
            # the verify program, on the hidden states it ended in)
            with span("apex.serve.decode.build"):
                toks = np.zeros(bb, np.int32)
                pos = np.zeros(bb, np.int32)
                sl = np.zeros(bb, np.int32)
                wbk = np.full(bb, DUMP_BLOCK, np.int32)
                wok = np.zeros(bb, np.int32)
                for i in full_rows:
                    toks[i] = reqs[i].out_tokens[-2]     # the token AT
                    pos[i] = base[i] + K             # position base+K
                    sl[i] = base[i] + T
                    wbk[i], wok[i] = slots[i][K]
                dfn = self._draft_decode_fn(bb, pb)
            with span("apex.serve.decode.dispatch"):
                self.draft_cache, _ = dfn(
                    self.draft_weights, self.draft_cache,
                    jnp.asarray(toks), jnp.asarray(pos), bt_j,
                    jnp.asarray(sl), jnp.asarray(wbk),
                    jnp.asarray(wok))
        with span("apex.serve.deliver"):
            self.decode_wall_s += dt
            self.decode_tokens += gained
            self.steps += 1
            self._event("decode_step", value=round(dt * 1e3, 3),
                        batch=n, batch_bucket=bb, pages_bucket=pb,
                        spec_proposed=tick_proposed,
                        spec_accepted=tick_accepted, tokens=gained)
        self._tick_tail(n, bb, pb)
        return gained

    def _tick_tail(self, batch: int, bb: int, pb: int) -> None:
        """Per-tick telemetry boundary: engine gauges on the
        registered cadence, snapshot-trigger poll, and the watchdog
        stall heartbeat — all host bookkeeping the engine already
        holds, after the tick's one device fetch."""
        with span("apex.serve.tick_tail"):
            levels = dict(
                batch=batch, batch_bucket=bb, pages_bucket=pb,
                free_blocks=self.manager.free_blocks,
                used_blocks=self.manager.used_blocks,
                reserved_blocks=self._reserved_blocks(),
                shared_blocks=self.manager.shared_blocks,
                pool_blocks=self.cache_cfg.usable_blocks,
                queue_depth=len(self.queue),
                prefilling=len(self.prefilling),
                compiles=sum(self._compiles.values()))
            if self.shed is not None and self.shed.enabled:
                levels["shed_engaged"] = self.shed.engaged
            if self.spec_disabled:
                levels["spec_disabled"] = True
            self.metrics.on_tick(self.steps, **levels)
            self._tick_levels = levels
            if self.journal is not None and self.active:
                # ONE aggregated progress record per tick (not one write
                # per request — the journal flushes per line, and O(batch)
                # syscalls per generated token would tax ITL): the replay
                # ledger's observability record (replay correctness rides
                # the submit/terminal records — greedy decode regenerates)
                self.journal.record_progress(
                    {rid: len(q.out_tokens)
                     for rid, q in self.active.items()}, self.steps)
            if self.snapshot is not None:
                self.snapshot.poll(self.steps, self.snapshot_state,
                                   self.monitor)
            # the serve loop's stall heartbeat: each tick feeds the same
            # Watchdog the training loops drive through StepMonitor, so a
            # wedged decode step raises the once-per-episode stall alarm
            # (with the optional jax.profiler capture) mid-serve
            wd = getattr(self.monitor, "watchdog", None)
            if wd is not None:
                wd.observe_step(self.steps)
            # ISSUE-17: SLO burn evaluation, then one lock-free exporter
            # publish — SLO first so the published /healthz already
            # reflects an episode that opened this tick
            if self.slo is not None:
                self._poll_slo()
            if self.exporter is not None:
                self._publish_exporter()

    def _poll_slo(self) -> None:
        """Per-tick SLO boundary: lazily emit the objective-
        definition event (guaranteed to precede any burn — the
        pairing ``trace_check --serve`` asserts), then forward the
        tracker's episode transitions: ``burn`` through the
        watchdog's alarm machinery (sink + escalation hook, once per
        episode — the tracker latches), ``recovered`` as a plain
        ``slo`` event."""
        if not self._slo_defined:
            self._slo_defined = True
            if self.monitor is not None:
                self.monitor.event("slo", "slo_objectives",
                                   step=self.steps,
                                   **self.slo.objectives_attrs())
        wd = getattr(self.monitor, "watchdog", None)
        for tr in self.slo.evaluate(self.steps):
            action = tr.pop("action")
            if action == "burn":
                if wd is not None:
                    wd.alarm("slo_burn", value=tr["burn_fast"],
                             step=self.steps, **tr)
                elif self.monitor is not None:
                    self.monitor.event("alarm", "slo_burn",
                                       value=tr["burn_fast"],
                                       step=self.steps, **tr)
            elif self.monitor is not None:
                self.monitor.event("slo", "slo_recovered",
                                   value=tr["burn_fast"],
                                   step=self.steps, **tr)

    def health_state(self, *, drained: bool = False) -> Dict[str, Any]:
        """The /healthz payload: ``ok`` is False while the engine is
        draining (SIGTERM / escalation / API), after an escalation
        was handled, or while any SLO episode burns.  Shedding is
        DEGRADED-but-serving — reported, still 200 (the healthz
        semantics table in docs/api/observability.md)."""
        draining = bool(drained or self._drain_reason is not None
                        or self._terminating())
        shed = bool(self.shed.engaged) if (
            self.shed is not None and self.shed.enabled) else False
        burning = list(self.slo.burning) if self.slo is not None \
            else []
        ok = not (draining or self._esc_handled or burning)
        status = ("draining" if draining
                  else "escalated" if self._esc_handled
                  else "slo_burning" if burning
                  else "shedding" if shed else "ok")
        return {
            "ok": ok, "status": status, "tick": self.steps,
            "replica": self.replica_id,
            "draining": draining, "shed_engaged": shed,
            "escalated": self._esc_handled,
            "slo_burning": burning,
            "active": len(self.active), "queued": len(self.queue),
        }

    def export_registry(self,
                        registry: Optional[MetricsRegistry] = None
                        ) -> MetricsRegistry:
        """Adapter shim: fill a :class:`~apex_tpu.monitor.export.
        MetricsRegistry` from bookkeeping the engine already holds —
        the gauge layer's last-tick levels
        (``EngineGauges.router_snapshot()``, no cadence advance), the
        metrics layer's lifetime terminal/rejection tallies, cached
        latency quantiles, SLO episode counters, and the watchdog's
        fired-alarm counts.  No second bookkeeping path and no device
        fetch; counters mirror (``set``) the cumulative values, they
        never re-count.  A fleet passes a shared ``registry`` so N
        replicas land in one exposition document under their
        ``replica`` labels."""
        reg = registry if registry is not None else MetricsRegistry()
        lbl = ({"replica": self.replica_id}
               if self.replica_id is not None else {})
        m = self.metrics
        c = reg.counter("apex_tpu_serve_requests_total",
                        "Terminal requests by terminal reason.")
        for terminal, n in sorted(m.terminals.items()):
            c.set(n, terminal=terminal, **lbl)
        gen = self._done_tokens \
            + sum(len(q.out_tokens) for q in self.active.values())
        reg.counter("apex_tpu_serve_tokens_total",
                    "Generated tokens over terminal requests."
                    ).set(self._done_tokens, **lbl)
        reg.gauge("apex_tpu_serve_tokens_live",
                  "Generated tokens including in-flight requests."
                  ).set(gen, **lbl)
        reg.counter("apex_tpu_serve_prefill_tokens_total",
                    "Prompt tokens prefilled."
                    ).set(self.prefill_tokens, **lbl)
        rej = reg.counter("apex_tpu_serve_rejected_total",
                          "Submits the engine refused, by reason.")
        for reason, n in sorted(m.rejected.items()):
            rej.set(n, reason=reason, **lbl)
        snap = m.gauges.router_snapshot()
        for key, help_text in (
                ("queue_depth", "Admission queue depth at the last "
                                "tick."),
                ("free_blocks", "Free KV pool blocks at the last "
                                "tick."),
                ("used_blocks", "Used KV pool blocks at the last "
                                "tick."),
                ("reserved_blocks", "Blocks reserved by admitted "
                                    "requests at the last tick."),
                ("pool_blocks", "Usable KV pool blocks."),
                ("prefilling", "Requests mid-chunked-prefill at the "
                               "last tick."),
                ("batch", "Decode batch at the last tick."),
                ("used_blocks_high_water", "Used-block high water."),
                ("last_tick", "Engine tick of the last gauge "
                              "window.")):
            if key in snap:
                name = ("apex_tpu_serve_tick" if key == "last_tick"
                        else f"apex_tpu_serve_{key}")
                reg.gauge(name, help_text).set(
                    float(snap[key] or 0), **lbl)
        reg.counter("apex_tpu_serve_compiles_total",
                    "Cumulative compiled-program count."
                    ).set(sum(self._compiles.values()), **lbl)
        reg.gauge("apex_tpu_serve_shed_engaged",
                  "1 while the hysteresis shed policy is engaged."
                  ).set(1.0 if (self.shed is not None
                                and self.shed.engaged) else 0.0,
                        **lbl)
        pct = m.percentiles_cached()
        q = reg.gauge("apex_tpu_serve_latency_ms",
                      "Serving latency quantiles over the bounded "
                      "sample windows.")
        for series in ("queue_wait", "ttft", "itl"):
            for quant in ("p50", "p99"):
                v = pct.get(f"{series}_{quant}_ms")
                if v is not None:
                    q.set(v, series=series, quantile=quant, **lbl)
        if self.slo is not None:
            reg.counter("apex_tpu_slo_burn_episodes_total",
                        "SLO burn-rate episodes tripped."
                        ).set(self.slo.episodes, **lbl)
            reg.gauge("apex_tpu_slo_burning",
                      "Currently-burning SLO episodes."
                      ).set(len(self.slo.burning), **lbl)
        wd = getattr(self.monitor, "watchdog", None)
        if wd is not None and hasattr(wd, "alarm_counts"):
            a = reg.counter("apex_tpu_alarm_episodes_total",
                            "Watchdog alarm episodes fired, by "
                            "class.")
            for name, n in sorted(wd.alarm_counts().items()):
                a.set(n, alarm=name, **lbl)
        return reg

    def _publish_exporter(self, *, drained: bool = False) -> None:
        """One lock-free exporter publish: registry + health + varz,
        all frozen at this tick.  Telemetry must never kill the
        serve."""
        try:
            self.exporter.publish(
                self.export_registry(), tick=self.steps,
                health=self.health_state(drained=drained),
                varz=self.snapshot_state())
        except Exception as e:
            logger.warning("exporter publish failed: %s",
                           str(e)[:160])

    def tokens_digest(self) -> str:
        """Deterministic digest of every request's output token
        stream — the cheap cross-run identity proof the CI spec leg
        compares against the plain leg (same submitted trace + same
        digest == token-for-token identical output)."""
        import hashlib

        h = hashlib.md5()
        allq = list(self.done) + list(self.active.values())
        for q in sorted(allq, key=lambda q: str(q.rid)):
            h.update(f"{q.rid}:"
                     f"{','.join(map(str, q.out_tokens))};".encode())
        return h.hexdigest()[:12]

    def digest_rows(self) -> Dict[str, List[int]]:
        """The raw material of :meth:`tokens_digest` as data —
        ``{rid: output tokens}`` for every request this engine holds.
        The process-fleet supervisor (ISSUE-18) merges these rows
        across replicas AND across a restarted replica's journal
        terminals into ONE routing-invariant fleet digest: greedy
        decode is batching/interleaving-invariant (the PR 15 sweep's
        proof), so the merged digest is identical no matter which
        replica served which rid or how a crash reshuffled them."""
        allq = list(self.done) + list(self.active.values())
        return {str(q.rid): [int(t) for t in q.out_tokens]
                for q in allq}

    def router_snapshot(self) -> Dict[str, Any]:
        """The cheap per-replica struct a fleet router load-balances
        on (ISSUE-14): pool headroom (free + reclaimable-idle blocks,
        net reservations), backlog (queue depth + mid-prefill jobs +
        running batch), shed state, the shared-prefix index's chain
        keys for sticky warm routing, and the gauge layer's last-tick
        view — all host bookkeeping the engine already holds, one
        dict, no device traffic and no reaching into engine
        internals."""
        snap = {
            "replica": self.replica_id,
            "tick": self.steps,
            "free_blocks": self.manager.free_blocks,
            "available_blocks": self.manager.available_blocks,
            "reserved_blocks": self._reserved_blocks(),
            "pool_blocks": self.cache_cfg.usable_blocks,
            "queue_depth": len(self.queue),
            "active": len(self.active),
            "prefilling": len(self.prefilling),
            "shed_engaged": bool(self.shed.engaged
                                 if self.shed is not None else False),
            # active SLO burn episodes ("class/dimension" strings) —
            # the per-class QoS admission door (ISSUE-18) gates on
            # these fleet-wide, so they ride the same poll
            "slo_burning": (list(self.slo.burning())
                            if self.slo is not None else []),
            "warm_prefix_keys": self.manager.prefix_keys(),
            "gauges": self.metrics.gauges.router_snapshot(),
            # cumulative counters the FleetAggregator differentiates
            # into rate series (tokens/tick, compile deltas) against
            # the measured tick delta — same host bookkeeping, one
            # dict, still no device traffic
            "tokens_generated": self._done_tokens
            + sum(len(q.out_tokens) for q in self.active.values()),
            "compiles": sum(self._compiles.values()),
            # each compiled decode program's kernel plan (static facts)
            "decode_plans": dict(self._decode_plans),
            # the trees the replica holds (target + draft), as held:
            # GPT weights in the compute dtype whatever was handed over
            "weight_bytes": sum(s["bytes_held"]
                                for s in self._held.values()),
        }
        return snap

    def swap_weights(self, weights: GPTServingWeights, *,
                     draft_weights=None) -> None:
        """Replace the serving weights IN PLACE on an idle engine —
        the per-replica half of the fleet's rolling swap.  The engine
        must be fully drained (no active/queued/mid-prefill work):
        the fleet router guarantees that by admit-stopping the replica
        first.  Weights are ARGUMENTS of the compiled programs, not
        closures, so every AOT-compiled ladder bucket survives the
        swap untouched — zero recompiles, which the sanitized CI swap
        leg asserts.  The incoming tree is held like the first
        (:func:`~.model.weights_in_compute_dtype`) BEFORE its leaves
        are compared with the serving arrays, so a freshly trained
        float32 tree swaps into an engine that holds bf16.  The KV
        pool and the shared-prefix index reset (every cached k/v row
        was computed under the OLD weights; serving it would silently
        mix models), so the first post-swap admissions run cold by
        design.

        A **requantization swap** (bf16 ``GPTServingWeights`` ↔ int8
        :class:`~apex_tpu.ops.quant_matmul.QuantGPTServingWeights`)
        changes the weight pytree's structure, so the cached target
        executables cannot survive; the engine drops them and re-runs
        the AOT warmup inside the drained swap window instead — every
        retrace is charged to the swap, and the steady state after the
        replica rejoins is still zero-recompile (the fleet rollout
        test asserts the compile counter is flat from rejoin on)."""
        if self.active or self.prefilling or self.queue:
            raise RuntimeError(
                f"swap_weights on a busy engine ({len(self.active)} "
                f"active, {len(self.prefilling)} prefilling, "
                f"{len(self.queue)} queued) — drain first (the "
                f"router's admit-stop → drain → swap sequence)")
        weights, held = _held_weights(weights, self.model_cfg)
        requantized = (jax.tree_util.tree_structure(self.weights)
                       != jax.tree_util.tree_structure(weights))
        if not requantized:
            jax.tree_util.tree_map(
                lambda old, new: _check_swap_leaf(old, new),
                self.weights, weights)
        else:
            if is_quantized_weights(weights) \
                    == is_quantized_weights(self.weights) \
                    or len(self.weights.layers) != len(weights.layers):
                raise ValueError(
                    "swap_weights pytree mismatch that is not a "
                    "bf16<->int8 requantization — a swap must keep "
                    "the model geometry (same layer count, same "
                    "embedding shapes)")
            # the unquantized leaves still obey the strict leaf rule
            for old, new in ((self.weights.wte, weights.wte),
                             (self.weights.wpe, weights.wpe),
                             (self.weights.lnf_w, weights.lnf_w)):
                _check_swap_leaf(old, new)
            self._decode_exec.clear()
            self._prefill_exec.clear()
            self._extend_exec.clear()
        if self.tp is not None:
            if requantized:
                self.tp = self.tp.rebind(
                    weight_quantized=is_quantized_weights(weights))
            weights = self.tp.shard_weights(weights)
        elif self.ep is not None:
            if requantized:
                raise ValueError(
                    "expert-parallel serving does not take int8 "
                    "weights — requantization swap refused")
            weights = self.ep.shard_weights(weights)
        elif self.device is not None:
            weights = jax.device_put(weights, self.device)
        self.weights = weights
        self._held["target"] = held
        if draft_weights is not None:
            if self.draft_weights is None:
                raise ValueError("draft_weights swap on an engine "
                                 "built without a draft")
            draft_weights, self._held["draft"] = _held_weights(
                draft_weights, self.draft_cfg)
            if self.device is not None:
                draft_weights = jax.device_put(draft_weights,
                                               self.device)
            self.draft_weights = draft_weights
        self.manager = KVCacheManager(
            self.cache_cfg, prefix_sharing=self.prefix_share)
        self.cache = self._fresh_cache()
        if self.draft_cache is not None:
            self.draft_cache = init_cache(self.draft_cache_cfg)
            if self.device is not None:
                self.draft_cache = jax.device_put(self.draft_cache,
                                                  self.device)
        if requantized:
            # restore the AOT ladder while still drained: the rebuild
            # is part of the swap's cost, not the steady state's
            self.warmup()
        self._event("weights_swapped", requantized=requantized,
                    compiles=sum(self._compiles.values()))
        self._emit_weights_held()

    def snapshot_state(self) -> Dict[str, Any]:
        """Live engine state as one JSON-able dict — what the
        on-demand :class:`~apex_tpu.serving.metrics.SnapshotTrigger`
        dumps as an ``engine_snapshot`` event for a wedged serve."""
        return {
            "tick": self.steps,
            "active": len(self.active),
            "queued": len(self.queue),
            "prefilling": [
                {"rid": str(rid), "written": job.written,
                 "prompt_len": len(job.tokens)}
                for rid, job in self.prefilling.items()],
            "done": self._done_count,
            "preempted": self._preempted_count,
            "free_blocks": self.manager.free_blocks,
            "used_blocks": self.manager.used_blocks,
            "reserved_blocks": self._reserved_blocks(),
            "shared_blocks": self.manager.shared_blocks,
            "idle_blocks": self.manager.idle_blocks,
            "used_blocks_high_water":
                self.metrics.gauges.used_blocks_hw,
            "pool_blocks": self.cache_cfg.usable_blocks,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "compiles": sum(self._compiles.values()),
            "requests": [
                {"rid": str(rid),
                 "seq_len": self.manager.seq_len(rid),
                 "new_tokens": len(q.out_tokens),
                 "max_new_tokens": q.max_new_tokens}
                for rid, q in sorted(self.active.items(),
                                     key=lambda kv: str(kv[0]))],
        }

    def run(self, *, max_steps: Optional[int] = None,
            before_tick: Optional[Callable[[int], None]] = None,
            after_tick: Optional[Callable[[int], None]] = None
            ) -> ServeSummary:
        """Serve until every submitted request finishes (or a
        termination request / ``max_steps`` drains the run).  On
        SIGTERM (via ``autoresume``) the engine stops admitting,
        abandons in-flight generation cleanly (blocks freed, requests
        marked preempted) and still returns a complete summary — the
        clean-drain contract CI kills a serve mid-run to prove.
        ``before_tick``/``after_tick`` receive the tick index (fault
        injection and the sanitizer's step boundary in the smoke
        driver).

        The summary covers the engine's **lifetime**: token/request
        totals accumulate across every ``run()`` call on this engine,
        and ``wall_s`` accumulates the time spent inside ``run()`` —
        so a paused-and-resumed serve (``max_steps``, or bench's
        staggered tail admissions) reports the same honest tokens/s
        as a single uninterrupted run, never lifetime tokens over
        one run's wall."""
        t0 = self._clock()
        drained = False
        try:
            while self.queue or self.active or self.prefilling:
                if self._terminating() or self._drain_reason is not None:
                    drained = True
                    self._drain(self._drain_reason
                                or (self.autoresume.source
                                    if self.autoresume else "api"))
                    break
                if max_steps is not None and self.steps >= max_steps:
                    drained = True
                    break
                if before_tick is not None:
                    before_tick(self.steps)
                self.step()
                if after_tick is not None:
                    after_tick(self.steps)
        except KeyboardInterrupt:
            # bare ^C with no AutoResume installed (the library-use
            # case): drain like SIGTERM — blocks freed, every chain
            # terminal, summary still returned — instead of unwinding
            # through the tick loop with blocks allocated.  A second
            # ^C during the drain propagates (the PR-3 double-signal
            # convention: the second one means NOW).
            drained = True
            self._drain("KeyboardInterrupt")
        # a drain request that became moot (everything finished in the
        # same tick that latched it, or max_steps broke first) must
        # not leak into a future run() on this engine and preempt a
        # fresh batch at its first tick
        self._drain_reason = None
        if self._esc_handled:
            # the handled episode ends with this run: consume the
            # policy latch and re-arm, so a future run() on this
            # engine escalates a NEW alarm instead of being deaf
            self._esc_handled = False
            if self.escalation is not None:
                self.escalation.reset()
        self._run_wall_s += self._clock() - t0
        # a trailing partial gauge window (tick_every > 1) flushes so
        # the final engine state is always in the log
        self.metrics.flush_gauges(self.steps)
        # final exporter publish: terminal counters complete, and the
        # published /healthz keeps reporting the drain until the
        # server stops (the CI flip probe reads this window)
        if self.exporter is not None:
            self._publish_exporter(drained=drained)
        summary = self.summary(drained=drained)
        self._event("serve_done", value=summary.tokens_per_sec,
                    **{k: v for k, v in summary.as_dict().items()
                       if k not in ("compiles", "tokens_per_sec")})
        return summary

    def summary(self, *, drained: bool = False) -> ServeSummary:
        """The engine's lifetime :class:`ServeSummary` from the
        counters it already holds — what :meth:`run` returns (and
        emits as ``serve_done``), exposed separately so a fleet can
        collect per-replica summaries without forcing an idle
        ``run()`` round per replica."""
        wall = max(self._run_wall_s, 1e-9)
        gen = self._done_tokens \
            + sum(len(q.out_tokens) for q in self.active.values())
        pct = self.metrics.percentiles()
        return ServeSummary(
            requests_done=self._done_count,
            requests_preempted=self._preempted_count,
            tokens_generated=gen,
            prefill_tokens=self.prefill_tokens,
            wall_s=round(wall, 4),
            decode_steps=self.steps,
            tokens_per_sec=round(gen / wall, 2),
            decode_wall_s=round(self.decode_wall_s, 4),
            decode_tokens_per_sec=round(
                self.decode_tokens / max(self.decode_wall_s, 1e-9), 2)
            if self.decode_tokens else 0.0,
            latency_p50_ms=_round_ms(_percentile(self._latencies, 50)),
            latency_p99_ms=_round_ms(_percentile(self._latencies, 99)),
            compiles=dict(self._compiles),
            drained=drained,
            queue_wait_p50_ms=pct["queue_wait_p50_ms"],
            queue_wait_p99_ms=pct["queue_wait_p99_ms"],
            ttft_p50_ms=pct["ttft_p50_ms"],
            ttft_p99_ms=pct["ttft_p99_ms"],
            itl_p50_ms=pct["itl_p50_ms"],
            itl_p99_ms=pct["itl_p99_ms"],
            requests_rejected=dict(self.metrics.rejected),
            spec_accept_rate=(
                round(self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed
                else (0.0 if self.speculate_k > 0 else None)),
            spec_tokens_proposed=self.spec_proposed,
            spec_tokens_accepted=self.spec_accepted,
            warm_prefix_admissions=self._warm_admissions,
            prefix_hit_tokens=self._prefix_hit_tokens,
            shared_blocks_hw=self.manager.shared_blocks_hw,
            cow_copies=self.manager.cow_copies,
            prefill_chunks=self.prefill_chunks,
            requests_deadline=self._deadline_count,
            requests_shed=self._shed_count,
            shed_engagements=(self.shed.engagements
                              if self.shed is not None else 0),
            spec_disabled=self.spec_disabled,
            replayed_requests=self._replayed,
            restarts=self.restarts,
            slo_burn_episodes=(self.slo.episodes
                               if self.slo is not None else 0),
            slo_recoveries=(self.slo.recoveries
                            if self.slo is not None else 0),
            slo_burning=(list(self.slo.burning)
                         if self.slo is not None else []))


def _held_weights(weights, cfg: ServingModelConfig):
    """``weights`` as an engine holds them, before sharding or
    placement (:func:`~.model.weights_in_compute_dtype`), and what that
    did: the attributes of the ``weights_held`` event."""
    held = weights_in_compute_dtype(weights, cfg)
    pairs = list(zip(jax.tree.leaves(weights), jax.tree.leaves(held)))
    return held, dict(
        leaves=len(pairs),
        leaves_cast=sum(h is not g for g, h in pairs),
        bytes_given=sum(g.size * g.dtype.itemsize for g, _ in pairs),
        bytes_held=sum(h.size * h.dtype.itemsize for _, h in pairs))


def _check_swap_leaf(old, new) -> None:
    """One weight leaf of a rolling swap: shape and dtype must match
    the serving arrays exactly, or the cached executables would
    retrace (shape change) or silently cast (dtype change)."""
    if old.shape != new.shape or old.dtype != new.dtype:
        raise ValueError(
            f"swap_weights leaf mismatch: serving "
            f"{old.shape}/{old.dtype} vs replacement "
            f"{new.shape}/{new.dtype} — a swap must keep the "
            f"compiled ladder valid (same geometry, same dtype)")


def _round_ms(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v * 1e3, 3)


def default_cache_config(model_cfg: ServingModelConfig,
                         num_blocks: Optional[int] = None,
                         block_size: Optional[int] = None,
                         kv_dtype: Optional[str] = None) -> KVCacheConfig:
    """Cache plan from the registered serving flags
    (``APEX_TPU_SERVE_KV_BLOCK`` / ``APEX_TPU_SERVE_KV_DTYPE`` /
    ``APEX_TPU_SERVE_BLOCKS``); explicit arguments override."""
    mla = model_cfg.mla
    eva = next((s for s in model_cfg.layers if s.chunk is not None), None)
    if eva is not None:
        if block_size not in (None, eva.chunk):
            raise ValueError(f"EVA layers of chunk {eva.chunk} are served "
                             f"from pages a chunk long, not {block_size}")
        block_size = eva.chunk
    return KVCacheConfig(
        # latent attention: one latent row a token and layer, and one
        # more layer for an MTP module that is served
        num_layers=model_cfg.num_layers + model_cfg.mtp_layers,
        value_dim=None if mla is None else mla.kv_rank,
        num_heads=model_cfg.num_kv_heads,
        head_dim=model_cfg.head_dim,
        num_blocks=(num_blocks if num_blocks is not None
                    else flag_int("APEX_TPU_SERVE_BLOCKS")),
        block_size=(block_size if block_size is not None
                    else flag_int("APEX_TPU_SERVE_KV_BLOCK")),
        kv_dtype=(kv_dtype if kv_dtype is not None
                  else flag_str("APEX_TPU_SERVE_KV_DTYPE")),
        model_dtype=model_cfg.dtype,
        # EVA layers: the pooled kind, window pages beside summary pages
        window=eva.window if eva is not None else None)
