"""apex_tpu.serving.fleet — multi-replica serving (ISSUE-14).

The "millions of users" story needs N engines behind a router, not
one.  This module is that host-side layer over the PR 9-13 serving
stack, four pieces:

* :class:`Replica` — one :class:`~.engine.ServingEngine` plus its
  fleet identity: a stable ``replica_id`` (stamped on every event the
  engine emits), a role (``serve`` decodes; ``prefill`` runs prompt
  admission only and streams finished KV to a decode replica), an
  optional per-replica :class:`~.resilience.RequestJournal` (a crashed
  replica recovers by crash_reset + replay, the PR-13 machinery), and
  the router's admit-stop latch (``routable``).
* :class:`FleetRouter` — the gauge-fed front: submissions are scored
  against each replica's :meth:`~.engine.ServingEngine.
  router_snapshot` (ONE cheap host struct per replica — free blocks
  net of in-flight reservations, backlog, shed state, and the shared
  prefix index's chain keys), with **sticky warm routing**: a prompt
  whose chain keys intersect a replica's warm-prefix keys routes
  there, so the CoW prefix machinery keeps paying across requests.
  ``APEX_TPU_SERVE_ROUTER`` picks the policy (``gauges`` default,
  ``round_robin`` the A/B control).
* **disaggregated prefill/decode** (:meth:`FleetRouter.submit` with
  prefill-role replicas) — the DistServe/Splitwise split: a prefill
  replica admits the prompt as a 1-token probe (the existing chunked-
  prefill/prefix-share path writes and registers every prompt page),
  then :func:`transfer_prefix` ships those pages —
  **block table as the wire format**, int8/bf16 storage bytes and
  scales preserved — into the decode replica's pool, registered into
  its shared index, so the real request's admission there is a WARM
  admission (``prefix_hit_tokens > 0``, the CI-asserted handoff
  proof).
* **rolling weight swap** (:meth:`FleetRouter.swap_weights`) — one
  replica at a time: admit-stop (the router routes around it), drain
  (in-flight requests finish normally — zero requests lost), swap
  (:meth:`~.engine.ServingEngine.swap_weights`: compiled ladder kept,
  KV pool reset), rejoin.  The fleet never drops below N−1 serving
  replicas and a sanitized fleet proves the swap compiles nothing.

Two drive modes: the deterministic **stepped** loop (one host thread
round-robins every replica's tick — CI, tests, disaggregation) and
the **threaded** mode (one thread per replica runs the engine's own
``run()``/supervised loop — the scaling measurement, since each
replica's jitted steps release the GIL and run concurrently on their
own device slice).  Driver: ``standalone_gpt --serve-fleet``;
aggregation: ``tools/trace_check.py --serve r0.jsonl r1.jsonl ...``
and the ``monitor_summary`` fleet digest.  Docs:
docs/api/serving.md#fleet-serving.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.flags import flag_str
from ..monitor.export import FleetAggregator, MetricsRegistry
from ..utils.log_util import get_logger
from .engine import Request, ServeSummary, ServingEngine
from .kv_cache import DUMP_BLOCK, prefix_chain_keys
from .model import gather_cache_blocks, scatter_cache_blocks
from .resilience import recover_engine, run_serving

logger = get_logger(__name__)

__all__ = ["FleetRouter", "FleetSummary", "Replica",
           "transfer_prefix", "export_prefix_payload",
           "import_prefix_payload"]

ROUTER_POLICIES = ("gauges", "round_robin")
# disaggregated prefill probes ride the normal request path under a
# namespaced rid so their lifecycle chains are ordinary, complete
# chains (N submitted => N terminal holds per replica log)
PREFILL_RID_PREFIX = "pf:"


@dataclasses.dataclass
class Replica:
    """One engine's seat in the fleet."""

    replica_id: str
    engine: ServingEngine
    role: str = "serve"               # 'serve' | 'prefill'
    journal: Any = None               # RequestJournal for recovery
    max_restarts: int = 3
    routable: bool = True             # router admit-stop latch
    restarts: int = 0                 # fleet-observed recoveries
    # deterministic fault injector (resilience.faults.FaultInjector)
    # fired at THIS replica's tick boundaries — how the CI fleet leg
    # crashes one replica while the others keep serving
    fault: Any = None

    def __post_init__(self):
        if self.role not in ("serve", "prefill"):
            raise ValueError(f"role {self.role!r} not in "
                             f"('serve', 'prefill')")
        if self.engine.replica_id is None:
            self.engine.replica_id = str(self.replica_id)
        if self.journal is not None and self.engine.journal is None:
            self.engine.journal = self.journal

    @property
    def busy(self) -> bool:
        e = self.engine
        return bool(e.queue or e.active or e.prefilling)

    def device_scope(self):
        """``jax.default_device`` pinned to this replica's device.

        The engine's per-tick input staging (``jnp.asarray`` of block
        tables, tokens, write slots) otherwise lands on the process
        default device — EVERY replica's every tick would then transit
        device 0's stream and the fleet serializes behind it (measured:
        flat aggregate tokens/s at any replica count).  Scoping each
        replica's ticks to its own device restores linear scaling; a
        replica without a pinned device (or a TP replica, whose mesh
        owns placement) gets a null scope."""
        dev = getattr(self.engine, "device", None)
        if dev is None:
            return contextlib.nullcontext()
        import jax as _jax

        return _jax.default_device(dev)


@dataclasses.dataclass
class FleetSummary:
    """What one fleet serve measured (the ``--serve-fleet`` /
    bench-row source).  Aggregates are over SERVE-role replicas
    (prefill probes are plumbing, not throughput); ``per_replica``
    carries every engine's full :class:`~.engine.ServeSummary`."""

    replicas: int
    prefill_replicas: int
    router_policy: str
    requests_submitted: int
    requests_done: int
    requests_preempted: int
    requests_deadline: int
    requests_shed: int
    lost_requests: int            # submitted - terminal; MUST be 0
    tokens_generated: int
    wall_s: float
    tokens_per_sec: float         # aggregate: fleet tokens over wall
    # capacity view: sum of per-replica decode-tick rates (each
    # replica's decode_wall counts only its own jitted steps)
    sum_decode_tokens_per_sec: float
    swaps: int = 0
    handoffs: int = 0             # disaggregated KV transfers done
    handoff_blocks: int = 0       # pages shipped (the wire volume)
    # worst serve-replica TTFT percentiles (each replica's bounded
    # window; the fleet reports the WORST replica — the SLO view)
    ttft_p50_ms: Optional[float] = None
    ttft_p99_ms: Optional[float] = None
    warm_prefix_admissions: int = 0
    prefix_hit_tokens: int = 0
    sticky_routes: int = 0        # submissions won by warm affinity
    replayed_requests: int = 0
    restarts: int = 0
    threaded: bool = False
    per_replica: Dict[str, dict] = dataclasses.field(
        default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Disaggregated KV handoff: block table as the wire format
# ---------------------------------------------------------------------------

# module-level jitted transfer pair: one compile per (cache shape,
# padded page count) across every handoff in the process — a fresh
# jax.jit per call would retrace per transfer
_gather_jit = jax.jit(gather_cache_blocks)
_scatter_jit = functools.partial(jax.jit, donate_argnums=(0,))(
    scatter_cache_blocks)


def _payload_shardings(dst: ServingEngine):
    """Where a stacked ``(L, n, ...)`` handoff payload goes on ``dst``:
    ``(k/v sharding, scales' sharding or None)``, each a cache leaf's
    own sharding with an unsharded layer axis in front (``dst`` may be
    another device, or a TP shard layout — the leaf describes both)."""
    from jax.sharding import NamedSharding, PartitionSpec

    def stacked(leaves):
        if leaves is None:
            return None
        sh = leaves[0].sharding
        if isinstance(sh, NamedSharding):
            return NamedSharding(sh.mesh, PartitionSpec(None, *sh.spec))
        return sh

    return stacked(dst.cache.k), stacked(dst.cache.k_scale)


def _geometry_key(cfg) -> tuple:
    return (cfg.num_layers, cfg.num_heads, cfg.head_dim,
            cfg.block_size, cfg.kv_dtype, str(cfg.storage_dtype))


def transfer_prefix(src: ServingEngine, dst: ServingEngine,
                    prompt: Sequence[int], *,
                    monitor=None) -> Optional[int]:
    """Ship ``prompt``'s resident KV pages from ``src``'s pool into
    ``dst``'s — the disaggregated prefill→decode handoff.

    The wire format is the block table itself: ``src``'s shared index
    names the pages (every full block plus the partial tail),
    :func:`~.model.gather_cache_blocks` pulls them as one
    ``(L, n, hk, bs, dk)`` payload in storage layout (int8 rows ship
    with their fp32 scales, bf16 ships bf16 — nothing requantizes),
    ``dst`` claims ``n`` pool blocks via :meth:`~.kv_cache.
    KVCacheManager.register_external` (indexed shared, parked idle —
    exactly a finished local request's state), and
    :func:`~.model.scatter_cache_blocks` lands the payload.  The next
    admission of this prompt on ``dst`` maps the pages WARM.

    Both pools are padded to ``dst``'s page ladder, so repeated
    handoffs of rung-sized spans reuse one compiled gather/scatter
    pair per rung.  Returns the page count shipped, 0 when ``dst``
    already had the prompt resident (no device traffic), or None when
    ``src`` does not hold the whole prompt (the caller falls back to
    a cold admission)."""
    if _geometry_key(src.cache_cfg) != _geometry_key(dst.cache_cfg):
        raise ValueError(
            f"KV handoff across incompatible cache geometries: "
            f"{_geometry_key(src.cache_cfg)} -> "
            f"{_geometry_key(dst.cache_cfg)}")
    src_blocks = src.manager.resident_prefix(prompt)
    if src_blocks is None:
        return None
    n = len(src_blocks)
    dst_blocks = dst.manager.register_external(prompt, n)
    if dst_blocks is None:
        return 0                       # already resident — warm as-is
    # pad both tables to dst's page rung: the padding gathers dump-
    # page zeros and scatters them back into dst's dump page — dead
    # bytes into a dead page, and one compile covers the whole rung
    pn = dst.ladder.pick_pages(n)
    sb = np.full(pn, DUMP_BLOCK, np.int32)
    db = np.full(pn, DUMP_BLOCK, np.int32)
    sb[:n] = src_blocks
    db[:n] = dst_blocks
    k, v, ks, vs = _gather_jit(src.cache, jnp.asarray(sb))
    # the wire hop: the payload leaves src's device for dst's pool
    sharding, ks_sh = _payload_shardings(dst)
    k, v = jax.device_put(k, sharding), jax.device_put(v, sharding)
    if ks is not None:
        ks = jax.device_put(ks, ks_sh)
        vs = jax.device_put(vs, ks_sh)
    with contextlib.ExitStack() as stack:
        dev = getattr(dst, "device", None)
        if dev is not None:
            stack.enter_context(jax.default_device(dev))
        dst.cache = _scatter_jit(dst.cache, k, v, ks, vs,
                                 jnp.asarray(db))
    if monitor is not None:
        monitor.event("fleet", "kv_handoff", value=n,
                      pages=n, padded=pn,
                      prompt_tokens=len(prompt),
                      src=str(src.replica_id),
                      dst=str(dst.replica_id))
    return n


def export_prefix_payload(src: ServingEngine, prompt: Sequence[int]
                          ) -> Optional[tuple]:
    """The source half of :func:`transfer_prefix` as HOST data — the
    process-fleet wire format (ISSUE-18).  Gathers ``prompt``'s
    resident pages exactly as the in-process handoff does (same
    ``_gather_jit``, same rung padding, int8 rows + fp32 scales
    verbatim) but lands them as numpy arrays a socket can carry.
    Returns ``(n, arrays)`` with ``arrays`` mapping ``k``/``v`` (and
    ``ks``/``vs`` for quantized storage) to host ndarrays padded to
    ``src.ladder.pick_pages(n)``, or None when ``src`` does not hold
    the whole prompt (the caller falls back to a cold admission)."""
    src_blocks = src.manager.resident_prefix(prompt)
    if src_blocks is None:
        return None
    n = len(src_blocks)
    pn = src.ladder.pick_pages(n)
    sb = np.full(pn, DUMP_BLOCK, np.int32)
    sb[:n] = src_blocks
    k, v, ks, vs = _gather_jit(src.cache, jnp.asarray(sb))
    arrays = {"k": np.asarray(k), "v": np.asarray(v)}
    if ks is not None:
        arrays["ks"] = np.asarray(ks)
        arrays["vs"] = np.asarray(vs)
    return n, arrays


def import_prefix_payload(dst: ServingEngine, prompt: Sequence[int],
                          n: int, arrays: Dict[str, Any]) -> int:
    """The destination half of :func:`transfer_prefix` from HOST data
    (ISSUE-18 socket handoff): claim ``n`` pool blocks via
    ``register_external`` and scatter the payload produced by
    :func:`export_prefix_payload`.  Both replicas must share the
    cache geometry AND the page ladder (one :class:`EngineSpec` per
    fleet guarantees it); a payload whose padded page count does not
    match this side's rung is rejected — the caller treats it as a
    torn handoff and admits cold.  Returns the page count landed, or
    0 when the prompt was already resident (no device traffic)."""
    pn = dst.ladder.pick_pages(int(n))
    if int(arrays["k"].shape[1]) != pn:
        raise ValueError(
            f"KV payload padded to {int(arrays['k'].shape[1])} "
            f"page(s) but this replica's ladder pads {n} -> {pn}: "
            f"mismatched page ladders across the fleet")
    dst_blocks = dst.manager.register_external(prompt, int(n))
    if dst_blocks is None:
        return 0                       # already resident — warm as-is
    db = np.full(pn, DUMP_BLOCK, np.int32)
    db[:n] = dst_blocks
    sharding, ks_sh = _payload_shardings(dst)
    k = jax.device_put(jnp.asarray(arrays["k"]), sharding)
    v = jax.device_put(jnp.asarray(arrays["v"]), sharding)
    ks = vs = None
    if "ks" in arrays:
        ks = jax.device_put(jnp.asarray(arrays["ks"]), ks_sh)
        vs = jax.device_put(jnp.asarray(arrays["vs"]), ks_sh)
    with contextlib.ExitStack() as stack:
        dev = getattr(dst, "device", None)
        if dev is not None:
            stack.enter_context(jax.default_device(dev))
        dst.cache = _scatter_jit(dst.cache, k, v, ks, vs,
                                 jnp.asarray(db))
    return int(n)


# ---------------------------------------------------------------------------
# The router
# ---------------------------------------------------------------------------

class FleetRouter:
    """Host-side front over N replicas: scored submission, sticky
    warm routing, disaggregated prefill, rolling weight swap, and the
    stepped / threaded fleet drive loops.  See the module docstring
    for the architecture; ``docs/api/serving.md#fleet-serving`` for
    the worked walkthroughs."""

    def __init__(self, replicas: Sequence[Replica], *,
                 policy: Optional[str] = None, monitor=None,
                 aggregator: Optional[FleetAggregator] = None,
                 exporter=None,
                 clock: Callable[[], float] = time.perf_counter):
        if not replicas:
            raise ValueError("a fleet needs at least one replica")
        ids = [r.replica_id for r in replicas]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate replica ids: {ids}")
        self.replicas = list(replicas)
        self.serve_replicas = [r for r in self.replicas
                               if r.role == "serve"]
        self.prefill_replicas = [r for r in self.replicas
                                 if r.role == "prefill"]
        if not self.serve_replicas:
            raise ValueError("a fleet needs at least one serve-role "
                             "replica (prefill replicas only feed)")
        sizes = {r.engine.cache_cfg.block_size for r in self.replicas}
        if len(sizes) != 1:
            raise ValueError(
                f"replicas disagree on the KV block size {sizes} — "
                f"prefix chain keys would not be comparable")
        self.block_size = sizes.pop()
        self.policy = policy if policy is not None \
            else (flag_str("APEX_TPU_SERVE_ROUTER") or "gauges")
        if self.policy not in ROUTER_POLICIES:
            raise ValueError(f"router policy {self.policy!r} not in "
                             f"{ROUTER_POLICIES}")
        if self.prefill_replicas:
            for r in self.replicas:
                if not r.engine.prefix_share:
                    raise ValueError(
                        f"disaggregated prefill needs "
                        f"prefix_share=True on every replica "
                        f"(replica {r.replica_id!r} has it off) — "
                        f"the handoff lands through the shared "
                        f"index")
        self.monitor = monitor
        # ISSUE-17 live metrics plane: the aggregator folds every
        # round's per-replica router_snapshot()s into fleet series
        # with trend windows (queue depth, free blocks net, backlog,
        # tokens/tick, compile deltas) and emits one ``fleet_tick``
        # event per router round; an attached exporter additionally
        # gets one published snapshot per round — per-replica series
        # under ``replica`` labels plus the fleet aggregates, all on
        # the router's single drive thread (no locks)
        self.aggregator = aggregator if aggregator is not None \
            else FleetAggregator()
        self.exporter = exporter
        self._clock = clock
        self._rr = 0
        self._pending: deque = deque()
        # submissions ROUTED but not yet engine-submitted (the
        # threaded drive plans every share before any engine sees a
        # request): counted into the backlog score, or every tied
        # snapshot would hand the whole batch to the first replica
        self._planned: Dict[str, int] = {}
        # rid -> (request, prefill replica): probes in flight
        self._handoffs: Dict[str, Any] = {}
        self.submitted = 0
        self.swaps = 0
        self.handoffs = 0
        self.handoff_blocks = 0
        self.sticky_routes = 0
        self.replayed = 0

    # --- events ---------------------------------------------------------

    def _event(self, name: str, value=None, **attrs) -> None:
        if self.monitor is not None:
            self.monitor.event("fleet", name, value=value, **attrs)

    # --- live metrics plane (ISSUE-17) ----------------------------------

    def fleet_tick(self, round_idx: int) -> Dict[str, Any]:
        """One aggregation round: gather every replica's
        ``router_snapshot()`` (the same cheap host struct routing
        already reads), fold it through the :class:`~apex_tpu.
        monitor.export.FleetAggregator`, emit ONE ``fleet_tick``
        event (step = router round, ``ticks`` = the measured engine-
        tick delta this window — the true rate denominator), and
        publish to the attached exporter.  Called once per stepped-
        loop round; the threaded drive calls it once after the join
        (its workers own their engines' ticks — aggregating from the
        drive thread only is the APX801 discipline)."""
        snapshots = {r.replica_id: r.engine.router_snapshot()
                     for r in self.replicas}
        attrs = self.aggregator.observe(round_idx, snapshots)
        if self.monitor is not None:
            self.monitor.event("fleet_tick", "fleet_tick",
                               value=attrs.get("queue_depth"),
                               step=round_idx, **attrs)
        if self.exporter is not None:
            try:
                self.exporter.publish(
                    self.fleet_registry(snapshots), tick=round_idx,
                    health=self.fleet_health(),
                    varz=self.fleet_varz())
            except Exception as e:  # telemetry must never kill serve
                logger.warning("fleet exporter publish failed: %s",
                               str(e)[:160])
        return attrs

    def fleet_registry(self,
                       snapshots: Optional[Dict[str, Dict[str, Any]]]
                       = None) -> "MetricsRegistry":
        """One exposition document for the whole fleet: every
        replica's engine series under its ``replica`` label plus the
        fleet-aggregate gauges and trend series."""
        reg = MetricsRegistry()
        for r in self.replicas:
            r.engine.export_registry(reg)
        if snapshots is None:
            snapshots = {r.replica_id: r.engine.router_snapshot()
                         for r in self.replicas}
        qd = sum(int(s.get("queue_depth", 0))
                 for s in snapshots.values())
        free_net = sum(int(s.get("available_blocks", 0))
                       - int(s.get("reserved_blocks", 0))
                       for s in snapshots.values())
        backlog = sum(int(s.get("queue_depth", 0))
                      + int(s.get("prefilling", 0))
                      + int(s.get("active", 0))
                      for s in snapshots.values())
        reg.gauge("apex_tpu_fleet_replicas",
                  "Serve-role replicas in the fleet."
                  ).set(len(self.serve_replicas))
        reg.gauge("apex_tpu_fleet_queue_depth",
                  "Fleet-wide admission queue depth.").set(qd)
        reg.gauge("apex_tpu_fleet_free_blocks_net",
                  "Fleet free+idle KV blocks net of reservations."
                  ).set(free_net)
        reg.gauge("apex_tpu_fleet_backlog",
                  "Fleet queued + prefilling + active requests."
                  ).set(backlog)
        c = reg.counter("apex_tpu_fleet_requests_routed_total",
                        "Requests the router submitted.")
        c.set(self.submitted)
        reg.counter("apex_tpu_fleet_kv_handoffs_total",
                    "Disaggregated prefill->decode KV handoffs."
                    ).set(self.handoffs)
        reg.counter("apex_tpu_fleet_swaps_total",
                    "Rolling weight swaps completed."
                    ).set(self.swaps)
        trend = reg.gauge("apex_tpu_fleet_trend",
                          "Windowed trend per fleet series "
                          "(least-squares slope / EWMA).")
        for series, t in self.aggregator.trends().items():
            trend.set(t["slope"], series=series, stat="slope")
            trend.set(t["ewma"], series=series, stat="ewma")
        return reg

    def fleet_health(self) -> Dict[str, Any]:
        """Fleet /healthz: ok iff every serve replica is ok; the
        worst replica's status wins the headline."""
        order = ("draining", "escalated", "slo_burning", "shedding",
                 "ok")
        per = {r.replica_id: r.engine.health_state()
               for r in self.replicas}
        ok = all(h["ok"] for h in per.values())
        worst = min((h["status"] for h in per.values()),
                    key=lambda s: order.index(s)
                    if s in order else 0, default="ok")
        return {"ok": ok, "status": worst,
                "replicas": {rid: h["status"]
                             for rid, h in sorted(per.items())}}

    def fleet_varz(self) -> Dict[str, Any]:
        return {rid: snap for rid, snap in sorted(
            (r.replica_id, r.engine.snapshot_state())
            for r in self.replicas)}

    # --- routing --------------------------------------------------------

    def _warm_tokens(self, snap: Dict[str, Any],
                     keys: List[bytes], pkey) -> int:
        """Prompt tokens a replica's warm-prefix keys already cover:
        consecutive full-block chain hits from the front (the chain
        property makes any later hit imply these), plus the partial
        tail when every full block hit."""
        index = snap.get("warm_prefix_keys") or ()
        tokens = 0
        hit_all = True
        for key in keys:
            if key in index:
                tokens += self.block_size
            else:
                hit_all = False
                break
        if hit_all and pkey is not None and pkey in index:
            tokens += 1               # partial tail resident too
        return tokens

    def route(self, request: Request) -> Replica:
        """Pick the serve replica for one submission.  ``gauges``
        policy: sticky warm affinity first (most prompt tokens already
        resident in a replica's prefix index), then pool headroom
        (free + idle blocks net of in-flight reservations), then the
        smallest backlog; shed-engaged replicas are avoided while any
        alternative exists.  ``round_robin`` ignores all signals (the
        A/B control)."""
        candidates = [r for r in self.serve_replicas if r.routable]
        if not candidates:
            raise RuntimeError(
                "no routable serve replica (every replica is "
                "admit-stopped) — rolling swap drains one at a time "
                "precisely so this cannot happen")
        if self.policy == "round_robin" or len(candidates) == 1:
            r = candidates[self._rr % len(candidates)]
            self._rr += 1
            return r
        keys, pkey = prefix_chain_keys(request.prompt,
                                       self.block_size)
        best = None
        best_score = None
        warm_best = 0
        for r in candidates:
            snap = r.engine.router_snapshot()
            warm = self._warm_tokens(snap, keys, pkey)
            headroom = (snap["available_blocks"]
                        - snap["reserved_blocks"])
            backlog = (snap["queue_depth"] + snap["prefilling"]
                       + snap["active"]
                       + self._planned.get(r.replica_id, 0))
            score = (0 if snap["shed_engaged"] else 1, warm,
                     headroom, -backlog)
            if best_score is None or score > best_score:
                best, best_score, warm_best = r, score, warm
        if warm_best > 0:
            self.sticky_routes += 1
        return best

    def submit(self, request: Request) -> Replica:
        """Route one request into the fleet.  With prefill-role
        replicas the submission disaggregates: the prompt runs on a
        prefill replica first (a 1-token probe under a ``pf:`` rid);
        its finished pages hand off to the decode replica this method
        already chose, and the REAL request submits there on arrival
        — a warm admission.  (Single-token prompts skip the split:
        there is nothing to transfer that the decode replica would
        not immediately rewrite.)"""
        target = self.route(request)
        if self.prefill_replicas and len(request.prompt) > 1:
            # anchor the request's clock NOW: its TTFT must count the
            # prefill-probe wait and the KV handoff, not restart at
            # the decode-side submit rounds later (the router and the
            # engines share the perf_counter timebase)
            if request.submit_t is None:
                request.submit_t = self._clock()
            pf = min(self.prefill_replicas,
                     key=lambda r: (len(r.engine.queue)
                                    + len(r.engine.prefilling)
                                    + len(r.engine.active)))
            probe = Request(rid=f"{PREFILL_RID_PREFIX}{request.rid}",
                            prompt=list(request.prompt),
                            max_new_tokens=1,
                            priority=request.priority)
            pf.engine.submit(probe)
            self._handoffs[probe.rid] = (request, pf, target)
            self.submitted += 1
            self._event("request_routed", rid=str(request.rid),
                        replica=target.replica_id,
                        prefill_replica=pf.replica_id,
                        disaggregated=True)
            return target
        target.engine.submit(request)
        self.submitted += 1
        self._event("request_routed", rid=str(request.rid),
                    replica=target.replica_id)
        return target

    def _advance_handoffs(self) -> None:
        """Complete any prefill probes whose prompt pages are fully
        written: transfer the pages to the chosen decode replica and
        submit the real request there (warm).  A probe that ended
        without registering its prompt (preempted/shed/deadline on
        the prefill side) falls back to a COLD submission — the
        request is never lost, it just pays the prefill again."""
        if not self._handoffs:
            return
        finished = []
        for pf_rid, (req, pf, target) in self._handoffs.items():
            probe = next((q for q in pf.engine.done
                          if str(q.rid) == pf_rid), None)
            if probe is None:
                continue
            finished.append(pf_rid)
            if not target.routable:
                target = self.route(req)
            shipped = transfer_prefix(pf.engine, target.engine,
                                      req.prompt,
                                      monitor=self.monitor)
            if shipped is not None:
                self.handoffs += 1
                self.handoff_blocks += shipped
            else:
                logger.warning(
                    "prefill probe %s finished but its prompt is not "
                    "resident on %s — cold fallback", pf_rid,
                    pf.replica_id)
            target.engine.submit(req)
        for pf_rid in finished:
            del self._handoffs[pf_rid]

    # --- rolling weight swap --------------------------------------------

    def swap_weights(self, weights, *,
                     drain_step: Optional[Callable[[], None]] = None
                     ) -> int:
        """Zero-downtime rolling swap: one serve replica at a time is
        admit-stopped, drained (its in-flight work finishes normally
        — ``drain_step`` advances the WHOLE fleet once per wait
        round, so the other N−1 replicas keep serving), swapped
        (compiled ladder kept, pool reset), and rejoined.  Prefill
        replicas swap after the serve side (their probes only feed).
        Returns the number of replicas swapped."""
        swapped = 0
        for r in self.serve_replicas + self.prefill_replicas:
            r.routable = False
            self._event("swap_drain", replica=r.replica_id,
                        active=len(r.engine.active),
                        queued=len(r.engine.queue))
            guard = 0
            while r.busy:
                if drain_step is not None:
                    drain_step()
                else:
                    self._step_replica(r)
                guard += 1
                if guard > 1_000_000:   # defensive: a wedged replica
                    raise RuntimeError(  # must not hang the swap
                        f"replica {r.replica_id} did not drain")
            r.engine.swap_weights(weights)
            swapped += 1
            self.swaps += 1
            r.routable = True
            self._event("swap_done", replica=r.replica_id,
                        swapped=swapped)
        return swapped

    # --- stepped drive loop ----------------------------------------------

    def _step_replica(self, r: Replica) -> None:
        """One engine tick with fleet-level crash supervision: a
        journaled replica that raises recovers in place
        (crash_reset + journal replay, bounded by ``max_restarts``);
        an unjournaled one propagates — the fleet must not silently
        eat an engine bug."""
        t0 = self._clock()
        try:
            with r.device_scope():
                if r.fault is not None:
                    r.fault.before_tick(
                        r.engine.steps,
                        journal_path=(r.journal.path
                                      if r.journal is not None
                                      else None))
                r.engine.step()
        except KeyboardInterrupt:
            raise
        except Exception as e:
            if r.journal is None or r.restarts >= r.max_restarts:
                raise
            logger.warning("replica %s crashed (%s: %s) — recovering "
                           "from its journal", r.replica_id,
                           type(e).__name__, str(e)[:120])
            r.restarts += 1
            self._event("replica_restart", replica=r.replica_id,
                        error=type(e).__name__,
                        message=str(e)[:160],
                        restarts=r.restarts)
            # the replica's OWN (replica-stamped) monitor carries the
            # replay events, so per-replica logs attribute correctly
            stats = recover_engine(r.engine, r.journal,
                                   monitor=r.engine.monitor)
            self.replayed += stats.replayed
        finally:
            # the stepped loop never enters engine.run(), which is
            # where _run_wall_s normally accrues — charge each tick's
            # wall here so per-replica ServeSummary wall_s and
            # tokens_per_sec stay honest in stepped fleets too
            r.engine._run_wall_s += self._clock() - t0

    def serve(self, requests: Sequence[Request] = (), *,
              swap_after: Optional[int] = None,
              swap_weights=None,
              max_rounds: Optional[int] = None,
              before_round: Optional[Callable[[int], None]] = None
              ) -> FleetSummary:
        """Drive the fleet to completion in the deterministic stepped
        loop: each round dispatches pending submissions (scored),
        completes ripe prefill→decode handoffs, then ticks every busy
        replica once.  ``swap_after`` triggers ONE rolling weight
        swap (to ``swap_weights``) after that many rounds — the other
        replicas keep ticking while each drains, which is the
        zero-downtime property the CI leg asserts.  Returns the
        aggregate :class:`FleetSummary`."""
        if swap_after is not None and swap_weights is None:
            raise ValueError("swap_after needs swap_weights")
        self._pending.extend(requests)
        t0 = self._clock()
        rounds = 0
        swapped = swap_after is None

        def tick_all():
            for r in self.replicas:
                if r.busy:
                    self._step_replica(r)

        while True:
            while self._pending:
                self.submit(self._pending.popleft())
            self._advance_handoffs()
            if not swapped and rounds >= swap_after:
                swapped = True
                self.swap_weights(swap_weights, drain_step=tick_all)
            busy = any(r.busy for r in self.replicas)
            if not busy and not self._pending and not self._handoffs:
                break
            if before_round is not None:
                before_round(rounds)
            tick_all()
            # one fleet_tick per router round, after the replicas
            # ticked: the aggregation window's ``ticks`` stamp counts
            # the engine ticks that actually elapsed (swap drains
            # advance engines without advancing rounds — the measured
            # delta, not the nominal cadence, is the rate denominator)
            self.fleet_tick(rounds)
            rounds += 1
            if max_rounds is not None and rounds >= max_rounds:
                break
        return self._summary(self._clock() - t0, threaded=False)

    # --- threaded drive loop ---------------------------------------------

    def serve_threaded(self, requests: Sequence[Request], *,
                       max_restarts: Optional[int] = None,
                       scheduler=None) -> FleetSummary:
        """One thread per serve replica, each running its engine's own
        ``run()`` (or the supervised :func:`~.resilience.run_serving`
        when the replica carries a journal).  Requests are routed
        up-front; each replica then serves its share concurrently —
        jitted steps release the GIL, so on a multi-core host the
        fleet's aggregate tokens/s scales with replica count (the
        bench's scaling row).  Disaggregation needs the stepped
        loop's handoff sequencing and is rejected here.

        ``scheduler`` (an :class:`apex_tpu.analysis.schedule.
        DeterministicScheduler`) gates every replica's tick boundary
        through a seeded permuted hand-off, serializing the threads
        in a reproducible interleaving — the race-hunting stress mode
        (``python -m apex_tpu.analysis.schedule``).  Worker threads
        write NO shared attributes: each deposits its supervised-run
        stats in its own slot of ``results`` and the main thread
        aggregates after ``join()`` (a cross-thread ``self.x += y``
        is exactly the APX801 lost-update race)."""
        if self.prefill_replicas:
            raise ValueError("disaggregated prefill runs in the "
                             "stepped loop (serve()), not threads")
        shares: Dict[str, List[Request]] = {
            r.replica_id: [] for r in self.serve_replicas}
        self._planned = {}
        for req in requests:
            target = self.route(req)
            shares[target.replica_id].append(req)
            self._planned[target.replica_id] = \
                self._planned.get(target.replica_id, 0) + 1
            self.submitted += 1
            self._event("request_routed", rid=str(req.rid),
                        replica=target.replica_id)
        self._planned = {}
        errors: List[BaseException] = []
        # one slot per replica id, one writer each; read after join()
        results: Dict[str, Tuple[int, int]] = {}
        workers = [r for r in self.serve_replicas
                   if shares[r.replica_id]]
        if scheduler is not None:
            for r in workers:
                scheduler.expect(r.replica_id)

        def worker(r: Replica, share: List[Request]) -> None:
            try:
                hooks = []
                if r.fault is not None:
                    jp = r.journal.path if r.journal is not None \
                        else None
                    hooks.append(lambda tick, _f=r.fault, _jp=jp:
                                 _f.before_tick(tick,
                                                journal_path=_jp))
                if scheduler is not None:
                    hooks.append(lambda tick, _rid=r.replica_id:
                                 scheduler.gate(_rid))
                before = None
                if hooks:
                    def before(tick, _hooks=tuple(hooks)):
                        for h in _hooks:
                            h(tick)
                no_retry: tuple = ()
                if scheduler is not None:
                    # a starved schedule gate is the HARNESS failing,
                    # not an engine crash: retrying it as one would
                    # mask the starvation behind max_restarts journal
                    # replays (each gating and starving again)
                    from ..analysis.schedule import ScheduleTimeout

                    no_retry = (ScheduleTimeout,)
                with r.device_scope():
                    if r.journal is not None:
                        res = run_serving(
                            r.engine, share, journal=r.journal,
                            max_restarts=(max_restarts
                                          if max_restarts is not None
                                          else r.max_restarts),
                            monitor=self.monitor,
                            before_tick=before,
                            no_retry_on=no_retry)
                        results[r.replica_id] = (res.restarts,
                                                 res.replayed)
                    else:
                        for req in share:
                            r.engine.submit(req)
                        r.engine.run(before_tick=before)
            except BaseException as e:
                # surfaced after the join: the fleet must collect
                # every worker before re-raising the first failure
                logger.error("replica %s worker failed: %s: %s",
                             r.replica_id, type(e).__name__,
                             str(e)[:160])
                errors.append(e)
            finally:
                if scheduler is not None:
                    scheduler.finish(r.replica_id)

        t0 = self._clock()
        threads = [threading.Thread(
            target=worker, args=(r, shares[r.replica_id]),
            name=f"replica-{r.replica_id}", daemon=True)
            for r in workers]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = self._clock() - t0
        for r in self.serve_replicas:
            got = results.get(r.replica_id)
            if got is not None:
                r.restarts += got[0]
                self.replayed += got[1]
        if errors:
            raise errors[0]
        # threaded mode has no router rounds — the workers owned
        # their engines' ticks.  One terminal aggregation round from
        # the drive thread (after the join: workers write no shared
        # state, the APX801 discipline) records the fleet's final
        # series and publishes the exporter's end state.
        self.fleet_tick(max((r.engine.steps for r in self.replicas),
                            default=0))
        return self._summary(wall, threaded=True)

    # --- aggregation ------------------------------------------------------

    def _summary(self, wall_s: float, *, threaded: bool
                 ) -> FleetSummary:
        per: Dict[str, ServeSummary] = {
            r.replica_id: r.engine.summary() for r in self.replicas}
        serve_ids = [r.replica_id for r in self.serve_replicas]
        tokens = sum(per[i].tokens_generated for i in serve_ids)
        terminal = sum(per[i].requests_done + per[i].requests_preempted
                       + per[i].requests_deadline
                       + per[i].requests_shed for i in serve_ids)
        wall = max(wall_s, 1e-9)
        summary = FleetSummary(
            replicas=len(self.serve_replicas),
            prefill_replicas=len(self.prefill_replicas),
            router_policy=self.policy,
            requests_submitted=self.submitted,
            requests_done=sum(per[i].requests_done
                              for i in serve_ids),
            requests_preempted=sum(per[i].requests_preempted
                                   for i in serve_ids),
            requests_deadline=sum(per[i].requests_deadline
                                  for i in serve_ids),
            requests_shed=sum(per[i].requests_shed
                              for i in serve_ids),
            lost_requests=self.submitted - terminal
            - len(self._handoffs),
            tokens_generated=tokens,
            wall_s=round(wall, 4),
            tokens_per_sec=round(tokens / wall, 2),
            sum_decode_tokens_per_sec=round(
                sum(per[i].decode_tokens_per_sec
                    for i in serve_ids), 2),
            swaps=self.swaps,
            handoffs=self.handoffs,
            handoff_blocks=self.handoff_blocks,
            ttft_p50_ms=max(
                (per[i].ttft_p50_ms for i in serve_ids
                 if per[i].ttft_p50_ms is not None),
                default=None),
            ttft_p99_ms=max(
                (per[i].ttft_p99_ms for i in serve_ids
                 if per[i].ttft_p99_ms is not None),
                default=None),
            warm_prefix_admissions=sum(
                per[i].warm_prefix_admissions for i in serve_ids),
            prefix_hit_tokens=sum(per[i].prefix_hit_tokens
                                  for i in serve_ids),
            sticky_routes=self.sticky_routes,
            replayed_requests=sum(per[i].replayed_requests
                                  for i in per),
            restarts=sum(r.restarts for r in self.replicas),
            threaded=threaded,
            per_replica={i: s.as_dict() for i, s in per.items()})
        self._event("fleet_done", value=summary.tokens_per_sec,
                    **{k: v for k, v in summary.as_dict().items()
                       if k != "per_replica"})
        return summary
