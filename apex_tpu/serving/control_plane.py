"""apex_tpu.serving.control_plane — the process-isolated fleet (ISSUE-18).

PR 14's fleet is N engine threads in ONE address space: a segfault,
OOM, or wedged XLA call in any replica takes down all of them.  This
module promotes every fleet boundary that is already *data* — the
request journal, the block-table KV wire format, ``router_snapshot()``
gauges — into a process/socket boundary:

* :class:`EngineSpec` — a picklable recipe for one replica's engine
  (builder entry point + model kwargs + device index + paths).  The
  parent never builds an engine; each **replica subprocess** does,
  pinned to its device, and speaks a length-prefixed-JSON(+binary)
  protocol over an AF_UNIX socket.
* :class:`ReplicaProcess` — the parent-side handle: spawn (``spawn``
  start method — fork after jax init is unsafe), hello handshake,
  sequenced RPCs with **explicit timeouts + bounded-backoff retry**
  (idempotent ops retry in place; non-idempotent ops — tick, submit,
  scatter — escalate to SIGKILL + respawn + journal replay, which the
  journal makes safe), and SIGKILL + join for the reap.
* :class:`ProcessFleet` — the supervisor: scored routing from gauge
  polls (a timed-out poll degrades that replica's score — it never
  blocks the tick), **heartbeat-supervised liveness** (missed polls ⇒
  SIGKILL + bounded-backoff restart, the PR 3 ``run_resumable``
  discipline), crash recovery by replaying the on-disk
  :class:`~.resilience.RequestJournal` into the fresh process (fleet
  digest token-identical to an uninterrupted run — greedy decode is
  batching-invariant, the PR 15 sweep's proof), disaggregated-prefill
  KV handoff over the socket (:func:`~.fleet.export_prefix_payload`
  blobs; a torn handoff falls back to cold prefill, never losing the
  request), **autoscaling** from FleetAggregator trend slopes
  (scale-up on backlog, drain-then-reap scale-down — zero lost
  requests), and **per-class QoS admission** tied to SLOTracker burn
  rates (:class:`QoSPolicy` over ShedPolicy's per-class thresholds).

The supervisor module itself imports no jax (importing the
``apex_tpu.serving`` package does pull jax into the parent
interpreter, but the parent creates no engines, no arrays, no device
state — all of that lives in the children, so one replica dying takes
nothing else with it).  KV blobs transit the parent as opaque bytes:
only children serialize/deserialize arrays.

Drive modes mirror the in-process fleet: the deterministic **stepped**
loop (faults, autoscale, QoS, handoffs; one supervisor round ticks
every replica once over RPC) and **freerun** (submit everything, send
one ``run`` RPC per replica, children decode concurrently in their own
processes — the scaling mode).

Supervision tree and the worked kill-9 walkthrough:
docs/api/resilience.md#distributed-control-plane.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import importlib
import json
import os
import random
import signal
import socket
import struct
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..analysis.flags import flag_float, flag_int
from ..monitor.events import Event, JsonlSink
from ..monitor.export import (FleetAggregator, MetricsExporter,
                              MetricsRegistry, MetricsServer,
                              replica_metrics_port)
from ..resilience.driver import backoff_delay
from ..resilience.faults import parse_fault, split_fault
from ..utils.log_util import get_logger
from .resilience import RequestJournal

logger = get_logger(__name__)

__all__ = [
    "AutoscalePolicy", "EngineSpec", "FleetGiveUp", "FrameError",
    "PROTOCOL", "ProcessFleet", "ProcessFleetSummary",
    "ProtocolSpec", "ProtocolViolation", "QoSClass", "QoSPolicy",
    "ReplicaProcess", "RpcError", "RpcRemoteError", "RpcTimeout",
    "ReplicaDead", "fleet_rows_digest", "recv_frame", "send_frame",
]

# disaggregated prefill probes ride the normal request path under this
# rid prefix (same convention as the in-process fleet) — probes are
# plumbing, excluded from fleet accounting and the fleet digest
PREFILL_RID_PREFIX = "pf:"

# one frame's JSON header may not exceed this (the KV payload rides
# separate binary blobs, so headers stay small; a corrupt length
# prefix must fail fast, not allocate gigabytes)
MAX_HEADER_BYTES = 64 << 20
MAX_BLOB_BYTES = 1 << 31


# ---------------------------------------------------------------------------
# Wire protocol: length-prefixed JSON header + raw binary blobs
# ---------------------------------------------------------------------------

class RpcError(RuntimeError):
    """Base class for control-plane RPC failures."""


class RpcTimeout(RpcError):
    """The peer did not answer within the per-op timeout.  For
    idempotent ops the caller retries with backoff; for the rest the
    supervisor escalates to SIGKILL + respawn + journal replay."""


class ReplicaDead(RpcError):
    """The socket died mid-conversation (peer closed, ECONNRESET) —
    the subprocess is gone or unreachable.  Supervisor restarts it."""


class RpcRemoteError(RpcError):
    """The child executed the op and reported a Python-level error.
    The connection is still healthy — this is a REQUEST-level failure
    (e.g. an engine admission reject), not a replica failure."""


class FrameError(RpcError):
    """The length prefix was honest but the header inside it was not
    JSON (or not a JSON object).  Crucially the stream is still
    FRAME-ALIGNED — exactly the declared bytes were consumed — so a
    receiver may answer with a structured error frame and keep
    serving instead of tearing the socket down."""


class ProtocolViolation(RpcError):
    """A frame that decodes fine but violates :data:`PROTOCOL`: an
    op nobody declared, a missing required header field, or a retry
    requested for a non-idempotent op.  Raised on the side that can
    see the violation — locally before a send, or remotely as a
    structured error reply."""


def send_frame(sock: socket.socket, header: Dict[str, Any],
               blobs: Sequence[bytes] = ()) -> None:
    """One wire frame: ``>I`` length + JSON header, then each binary
    blob verbatim (lengths announced in ``header['blobs']``).  KV
    payloads ride the blobs — int8 rows and fp32 scales as raw bytes,
    never JSON-escaped."""
    header = dict(header)
    if blobs:
        header["blobs"] = [len(b) for b in blobs]
    payload = json.dumps(header, separators=(",", ":")).encode()
    try:
        sock.sendall(struct.pack(">I", len(payload)) + payload)
        for b in blobs:
            sock.sendall(b)
    except socket.timeout as e:
        raise RpcTimeout(f"send timed out: {e}") from e
    except OSError as e:
        raise ReplicaDead(f"send failed: {e}") from e


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks: List[bytes] = []
    remaining = int(n)
    while remaining:
        try:
            chunk = sock.recv(min(remaining, 1 << 20))
        except socket.timeout as e:
            raise RpcTimeout(
                f"recv timed out with {remaining} byte(s) "
                f"outstanding") from e
        except OSError as e:
            raise ReplicaDead(f"recv failed: {e}") from e
        if not chunk:
            raise ReplicaDead("peer closed the socket"
                              + (" mid-frame" if chunks or
                                 remaining != n else ""))
        chunks.append(chunk)
        remaining -= len(chunk)
    return b"".join(chunks)


def recv_frame(sock: socket.socket
               ) -> Tuple[Dict[str, Any], List[bytes]]:
    """Receive one frame; returns ``(header, blobs)``.  Raises
    :class:`RpcTimeout` on the socket timeout, :class:`ReplicaDead`
    on EOF/reset, :class:`FrameError` on an undecodable header (the
    stream stays frame-aligned — the worker loop answers and keeps
    serving), and plain :class:`RpcError` when the framing itself is
    untrustworthy (corrupt length prefix, junk blob lengths — the
    only cure is a new socket)."""
    (n,) = struct.unpack(">I", _recv_exact(sock, 4))
    if n > MAX_HEADER_BYTES:
        raise RpcError(f"frame header of {n} bytes exceeds "
                       f"{MAX_HEADER_BYTES} — corrupt length prefix?")
    try:
        header = json.loads(_recv_exact(sock, n).decode())
    except ValueError as e:
        raise FrameError(f"malformed frame header: {e}") from e
    if not isinstance(header, dict):
        raise FrameError(f"frame header is "
                         f"{type(header).__name__}, not an object")
    blobs = []
    lengths = header.get("blobs", [])
    if not isinstance(lengths, list):
        raise RpcError(f"blob lengths {lengths!r} are not a list")
    for m in lengths:
        if not isinstance(m, int) or not 0 <= m <= MAX_BLOB_BYTES:
            raise RpcError(f"blob length {m!r} out of range")
        blobs.append(_recv_exact(sock, m))
    return header, blobs


# ---------------------------------------------------------------------------
# The protocol, as data: every op both sides are generated from
# ---------------------------------------------------------------------------

#: Header fields the FRAMING layer owns on every message — senders
#: may always set them, receivers may always read them, and no
#: :class:`ProtocolSpec` re-declares them: ``op``/``seq`` address the
#: frame, ``blobs`` carries the binary lengths (``send_frame`` adds
#: it), ``error``/``message`` are the structured error-reply shape.
FRAME_FIELDS = ("op", "seq", "blobs", "error", "message")

#: The timeout classes call sites must route through (never literal
#: floats): ``rpc`` = APEX_TPU_CP_RPC_TIMEOUT_S, ``poll`` =
#: APEX_TPU_CP_POLL_TIMEOUT_S, ``spawn`` = APEX_TPU_CP_SPAWN_TIMEOUT_S.
TIMEOUT_CLASSES = ("rpc", "poll", "spawn")


@dataclasses.dataclass(frozen=True)
class ProtocolSpec:
    """One wire op's declared contract.  The child's dispatch table,
    the parent's retry policy, and the per-op timeouts are all
    derived from these — and ``apex_tpu.analysis.protocol`` audits
    both sides against them statically (APX901–APX905).

    ``required``/``optional`` are the request header fields beyond
    :data:`FRAME_FIELDS`; ``reply`` the success-reply fields.
    ``request_blobs``/``reply_blobs`` declare which direction may
    carry binary payloads.  ``idempotent`` gates in-place retry:
    a non-idempotent op never re-sends — it escalates to
    SIGKILL + respawn + journal replay."""

    op: str
    direction: str = "parent_to_child"
    required: Tuple[str, ...] = ()
    optional: Tuple[str, ...] = ()
    reply: Tuple[str, ...] = ()
    request_blobs: bool = False
    reply_blobs: bool = False
    timeout_class: str = "rpc"
    idempotent: bool = False

    def __post_init__(self):
        if self.direction not in ("parent_to_child",
                                  "child_to_parent"):
            raise ValueError(f"bad direction {self.direction!r}")
        if self.timeout_class not in TIMEOUT_CLASSES:
            raise ValueError(
                f"timeout class {self.timeout_class!r} not in "
                f"{TIMEOUT_CLASSES}")
        clash = (set(self.required) | set(self.optional)
                 | set(self.reply)) & set(FRAME_FIELDS)
        if clash:
            raise ValueError(f"op {self.op!r} re-declares framing "
                             f"field(s) {sorted(clash)}")


#: op name → spec.  THE registry: ``_OP_HANDLERS`` must cover exactly
#: the parent→child ops here (checked at import), ``ReplicaProcess``
#: refuses undeclared ops and non-idempotent retries, and
#: ``ProcessFleet`` maps ``timeout_class``/``idempotent`` to its
#: configured deadlines and retry budgets.
PROTOCOL: Dict[str, ProtocolSpec] = {s.op: s for s in (
    ProtocolSpec("hello", direction="child_to_parent",
                 required=("rid", "pid"),
                 optional=("replayed", "tick", "block_size"),
                 timeout_class="spawn"),
    ProtocolSpec("snapshot", reply=("snapshot",),
                 timeout_class="poll", idempotent=True),
    ProtocolSpec("tick", reply=("tick", "busy", "finished")),
    ProtocolSpec("submit", required=("req",), reply=("ok",)),
    ProtocolSpec("gather_kv", required=("prompt",),
                 reply=("resident", "names", "shapes", "dtypes",
                        "geometry"),
                 reply_blobs=True, idempotent=True),
    ProtocolSpec("scatter_kv",
                 required=("names", "shapes", "dtypes", "prompt",
                           "n"),
                 optional=("geometry",), reply=("landed",),
                 request_blobs=True),
    ProtocolSpec("run", reply=("summary", "finished", "busy"),
                 timeout_class="spawn"),
    ProtocolSpec("summary",
                 reply=("summary", "digest", "rows", "replayed",
                        "tick"),
                 idempotent=True),
    ProtocolSpec("shutdown", idempotent=True),
)}


# ---------------------------------------------------------------------------
# EngineSpec: the picklable recipe a subprocess builds its engine from
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EngineSpec:
    """Everything a replica subprocess needs to build its engine.

    ``builder`` is a ``"module:function"`` entry point resolved IN THE
    CHILD (the parent never imports it); it receives this spec as a
    plain dict and returns ``{"engine": ..., "monitor": ..., or None,
    "journal": ... or None}``.  ``model`` carries the builder's
    kwargs verbatim.  ``fault`` is a child-side injector spec string
    (``kill9@K`` etc.) fired at the engine's tick boundaries;
    ``replay`` makes the fresh process re-enter its journal's open
    rids before serving (the crash-recovery spawn).
    ``connect_timeout_s`` is how long the child keeps retrying its
    rendezvous connect — :meth:`ReplicaProcess.begin_spawn` stamps
    it with the SAME ``spawn_timeout_s`` deadline the listener
    honors, so the two sides of the handshake can never race two
    different clocks (None falls back to the registered
    ``APEX_TPU_CP_CONNECT_TIMEOUT_S`` flag)."""

    replica_id: str
    role: str = "serve"                   # 'serve' | 'prefill'
    builder: str = ("apex_tpu.testing.standalone_gpt:"
                    "build_fleet_engine")
    model: Dict[str, Any] = dataclasses.field(default_factory=dict)
    device_index: Optional[int] = None
    jsonl_path: Optional[str] = None
    journal_path: Optional[str] = None
    metrics_port: Optional[int] = None
    fault: Optional[str] = None
    replay: bool = False
    connect_timeout_s: Optional[float] = None

    def __post_init__(self):
        if self.role not in ("serve", "prefill"):
            raise ValueError(f"role {self.role!r} not in "
                             f"('serve', 'prefill')")

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "EngineSpec":
        return EngineSpec(**d)


def _resolve_builder(path: str) -> Callable[[Dict[str, Any]],
                                            Dict[str, Any]]:
    mod, _, fn = path.partition(":")
    if not mod or not fn:
        raise ValueError(f"builder {path!r} is not 'module:function'")
    return getattr(importlib.import_module(mod), fn)


def fleet_rows_digest(rows: Dict[str, List[int]]) -> str:
    """The routing-invariant fleet digest: md5 over ``rid:tokens;``
    in sorted rid order, prefill probes excluded.  Identical row
    format to :meth:`~.engine.ServingEngine.tokens_digest`, but
    merged across every replica AND across a restarted replica's
    journal terminals — so a kill-9'd fleet and an uninterrupted one
    digest the same no matter how the crash reshuffled routing."""
    h = hashlib.md5()
    for rid in sorted(rows):
        if str(rid).startswith(PREFILL_RID_PREFIX):
            continue
        h.update(f"{rid}:"
                 f"{','.join(map(str, rows[rid]))};".encode())
    return h.hexdigest()[:12]


# ---------------------------------------------------------------------------
# Autoscale + QoS policies (pure host logic, unit-testable)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AutoscalePolicy:
    """Scale decisions from the signals PR 17's FleetAggregator
    already computes.  Scale UP when backlog-per-serve-replica crosses
    ``up_backlog`` while the ``queue_depth`` trend slope is
    non-improving (``>= up_slope``); scale DOWN after
    ``down_rounds`` consecutive rounds below ``down_backlog`` per
    replica.  ``cooldown`` rounds separate consecutive actions so one
    burst cannot thrash spawn/reap."""

    min_replicas: int = 1
    max_replicas: int = 4
    up_backlog: float = 4.0
    up_slope: float = 0.0
    down_backlog: float = 0.5
    down_rounds: int = 3
    cooldown: int = 3
    _idle_rounds: int = dataclasses.field(default=0, init=False)
    _last_action: int = dataclasses.field(default=-(10 ** 9),
                                          init=False)

    def __post_init__(self):
        if not 1 <= self.min_replicas <= self.max_replicas:
            raise ValueError(
                f"need 1 <= min_replicas <= max_replicas, got "
                f"{self.min_replicas}..{self.max_replicas}")

    def decide(self, round_idx: int, n_serve: int, backlog: int,
               trends: Optional[Dict[str, Dict[str, float]]]
               ) -> Optional[str]:
        """``'up'`` / ``'down'`` / None for this round."""
        per = float(backlog) / max(1, n_serve)
        slope = float(((trends or {}).get("queue_depth") or {})
                      .get("slope", 0.0))
        if per < self.down_backlog:
            self._idle_rounds += 1
        else:
            self._idle_rounds = 0
        if round_idx - self._last_action < self.cooldown:
            return None
        if (n_serve < self.max_replicas and per >= self.up_backlog
                and slope >= self.up_slope):
            self._last_action = round_idx
            self._idle_rounds = 0
            return "up"
        if (n_serve > self.min_replicas
                and self._idle_rounds >= self.down_rounds):
            self._last_action = round_idx
            self._idle_rounds = 0
            return "down"
        return None


@dataclasses.dataclass
class QoSClass:
    """One priority class's admission contract: ``max_open`` caps the
    class's fleet-wide in-flight requests (0 = defer to the
    ShedPolicy's per-class queue high-water mark), ``shed_on_burn``
    refuses new admissions while the class has an active SLO burn
    episode (the PR 17 SLOTracker signal, polled off the gauge
    snapshots)."""

    name: str
    max_open: int = 0
    shed_on_burn: bool = False


class QoSPolicy:
    """Per-priority-class admission at the fleet door.

    Classes are the engine's own naming (``p<priority>``,
    :meth:`~.metrics.ServeMetrics.priority_class`).  A refused request
    is SHED AT THE DOOR — it never reaches an engine, opens no
    lifecycle chain, and is accounted as ``shed_admission`` (so
    ``trace_check --serve``'s N submitted ⇒ N terminal still holds
    over what WAS submitted)."""

    def __init__(self, classes: Sequence[QoSClass] = (),
                 shed=None):
        self.classes: Dict[str, QoSClass] = {}
        for c in classes:
            if c.name in self.classes:
                raise ValueError(f"duplicate QoS class {c.name!r}")
            self.classes[c.name] = c
        self.shed = shed                  # ShedPolicy (queue_hw_for)

    @staticmethod
    def class_of(priority) -> str:
        return f"p{int(priority or 0)}"

    def admit(self, cls: str, open_count: int,
              burning: Sequence[str]) -> Tuple[bool, str]:
        """Admission verdict for one request of class ``cls`` given
        the class's fleet-wide open count and the active SLO burn
        episodes (``class/dimension`` strings)."""
        qc = self.classes.get(cls)
        cap = qc.max_open if qc is not None and qc.max_open else 0
        if not cap and self.shed is not None:
            cap = int(self.shed.queue_hw_for(cls))
        if cap and open_count >= cap:
            return False, "class_backlog"
        if qc is not None and qc.shed_on_burn and any(
                str(b).partition("/")[0] == cls for b in burning):
            return False, "slo_burn"
        return True, ""


class FleetGiveUp(RuntimeError):
    """A replica exhausted its restart budget (the bounded half of
    bounded-backoff restart — mirrors :class:`~..resilience.driver.
    GiveUp`)."""


# ---------------------------------------------------------------------------
# Child side: the replica worker
# ---------------------------------------------------------------------------

def _np_dtype(name: str):
    """Resolve a dtype string in the CHILD (numpy available there).
    ``bfloat16`` needs the ml_dtypes registration jax ships."""
    import numpy as np

    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _connect_child(path: str, timeout_s: float) -> socket.socket:
    deadline = time.monotonic() + timeout_s
    attempt = 0
    while True:
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            s.connect(path)
            return s
        except OSError:
            s.close()
            if time.monotonic() >= deadline:
                raise
            time.sleep(backoff_delay(attempt, base=0.02,
                                     maximum=0.5))
            attempt += 1


class _WorkerState:
    """Everything the child's RPC loop owns (ALL jax state lives
    here, in the subprocess)."""

    def __init__(self, spec: EngineSpec, built: Dict[str, Any]):
        self.spec = spec
        self.engine = built["engine"]
        self.monitor = built.get("monitor")
        self.journal = built.get("journal")
        self.closer = built.get("close")
        self.fault = parse_fault(spec.fault)
        self.replayed = 0
        self.done_mark = 0           # engine.done watermark
        self.exporter = None
        self.metrics_server = None

    def new_finished(self) -> List[List[str]]:
        """Terminal rids since the last report (the tick-reply
        delta the supervisor's ledger is built from)."""
        out = []
        done = self.engine.done
        while self.done_mark < len(done):
            q = done[self.done_mark]
            out.append([str(q.rid), str(q.terminal or "finished")])
            self.done_mark += 1
        return out

    def close(self) -> None:
        if self.metrics_server is not None:
            with contextlib.suppress(Exception):
                self.metrics_server.stop()
        if self.closer is not None:
            with contextlib.suppress(Exception):
                self.closer()
        for obj in (self.journal, self.monitor):
            if obj is not None:
                with contextlib.suppress(Exception):
                    obj.close()


def _build_worker(spec: EngineSpec) -> _WorkerState:
    builder = _resolve_builder(spec.builder)
    state = _WorkerState(spec, builder(spec.as_dict()))
    if spec.replay and state.journal is not None \
            and spec.journal_path \
            and os.path.exists(spec.journal_path):
        # the crash-recovery spawn: re-enter every open rid from the
        # on-disk ledger (PR 13 machinery — crash_reset on a fresh
        # engine is a no-op, resubmit opens a new lifecycle chain as
        # documented).  Probes replay like any request.
        from .resilience import recover_engine

        stats = recover_engine(state.engine, state.journal,
                               state.monitor)
        state.replayed = int(stats.replayed)
    if spec.metrics_port:
        state.exporter = MetricsExporter()
        state.metrics_server = MetricsServer(
            state.exporter, port=int(spec.metrics_port),
            monitor=state.monitor)
        state.metrics_server.start()
    return state


def _worker_publish(state: _WorkerState, tick: int) -> None:
    if state.exporter is None:
        return
    try:
        reg = MetricsRegistry()
        state.engine.export_registry(reg)
        state.exporter.publish(reg, tick=tick)
    except Exception as e:      # telemetry must never kill the serve
        logger.warning("replica exporter publish failed: %s",
                       str(e)[:160])


def _op_snapshot(state: _WorkerState, header: Dict[str, Any],
                 blobs: List[bytes]
                 ) -> Tuple[Dict[str, Any], List[bytes]]:
    snap = dict(state.engine.router_snapshot())
    # chain keys are bytes digests; hex them for the JSON header
    snap["warm_prefix_keys"] = [k.hex()
                                for k in snap["warm_prefix_keys"]]
    e = state.engine
    snap["busy"] = bool(e.queue or e.active or e.prefilling)
    _worker_publish(state, e.steps)
    return {"snapshot": snap}, []


def _op_tick(state: _WorkerState, header: Dict[str, Any],
             blobs: List[bytes]
             ) -> Tuple[Dict[str, Any], List[bytes]]:
    e = state.engine
    if state.fault is not None:
        state.fault.before_tick(e.steps,
                                journal_path=state.spec.journal_path)
    if e.queue or e.active or e.prefilling:
        e.step()
    return ({"tick": e.steps,
             "busy": bool(e.queue or e.active or e.prefilling),
             "finished": state.new_finished()}, [])


def _op_submit(state: _WorkerState, header: Dict[str, Any],
               blobs: List[bytes]
               ) -> Tuple[Dict[str, Any], List[bytes]]:
    from .engine import Request

    req = header["req"]
    state.engine.submit(Request(
        rid=str(req["rid"]),
        prompt=[int(t) for t in req["prompt"]],
        max_new_tokens=int(req.get("max_new_tokens", 1)),
        eos_token=req.get("eos_token"),
        deadline_ms=req.get("deadline_ms"),
        priority=int(req.get("priority", 0) or 0)))
    return {"ok": 1}, []


def _op_gather_kv(state: _WorkerState, header: Dict[str, Any],
                  blobs: List[bytes]
                  ) -> Tuple[Dict[str, Any], List[bytes]]:
    from .fleet import _geometry_key, export_prefix_payload

    out = export_prefix_payload(
        state.engine, [int(t) for t in header["prompt"]])
    if out is None:
        return {"resident": -1}, []
    n, arrays = out
    names = sorted(arrays)
    return ({"resident": int(n), "names": names,
             "shapes": [list(arrays[k].shape) for k in names],
             "dtypes": [str(arrays[k].dtype) for k in names],
             "geometry": list(map(str, _geometry_key(
                 state.engine.cache_cfg)))},
            [arrays[k].tobytes() for k in names])


def _op_scatter_kv(state: _WorkerState, header: Dict[str, Any],
                   blobs: List[bytes]
                   ) -> Tuple[Dict[str, Any], List[bytes]]:
    import numpy as np

    from .fleet import _geometry_key, import_prefix_payload

    geo = list(map(str, _geometry_key(state.engine.cache_cfg)))
    if list(header.get("geometry", geo)) != geo:
        raise ValueError(
            f"KV handoff across incompatible cache geometries: "
            f"{header.get('geometry')} -> {geo}")
    arrays = {}
    for name, shape, dtype, blob in zip(
            header["names"], header["shapes"], header["dtypes"],
            blobs):
        arrays[name] = np.frombuffer(
            blob, dtype=_np_dtype(dtype)).reshape(shape)
    landed = import_prefix_payload(
        state.engine, [int(t) for t in header["prompt"]],
        int(header["n"]), arrays)
    return {"landed": int(landed)}, []


def _op_run(state: _WorkerState, header: Dict[str, Any],
            blobs: List[bytes]
            ) -> Tuple[Dict[str, Any], List[bytes]]:
    e = state.engine

    def before_tick(tick):
        if state.fault is not None:
            state.fault.before_tick(
                tick, journal_path=state.spec.journal_path)

    summary = e.run(before_tick=before_tick)
    _worker_publish(state, e.steps)
    return ({"summary": summary.as_dict(),
             "finished": state.new_finished(),
             "busy": bool(e.queue or e.active or e.prefilling)}, [])


def _op_summary(state: _WorkerState, header: Dict[str, Any],
                blobs: List[bytes]
                ) -> Tuple[Dict[str, Any], List[bytes]]:
    e = state.engine
    return ({"summary": e.summary().as_dict(),
             "digest": e.tokens_digest(),
             "rows": e.digest_rows(),
             "replayed": state.replayed,
             "tick": e.steps}, [])


def _op_shutdown(state: _WorkerState, header: Dict[str, Any],
                 blobs: List[bytes]
                 ) -> Tuple[Dict[str, Any], List[bytes]]:
    # the loop special-cases shutdown (reply THEN return); the
    # handler exists so the dispatch table covers the whole registry
    return {}, []


#: The child dispatch, generated against :data:`PROTOCOL` — every
#: parent→child op maps to one uniform ``(state, header, blobs) →
#: (reply_fields, reply_blobs)`` handler.  ``_validate_protocol``
#: fails the import on any drift between this table and the registry.
_OP_HANDLERS: Dict[str, Callable[
    [_WorkerState, Dict[str, Any], List[bytes]],
    Tuple[Dict[str, Any], List[bytes]]]] = {
    "snapshot": _op_snapshot,
    "tick": _op_tick,
    "submit": _op_submit,
    "gather_kv": _op_gather_kv,
    "scatter_kv": _op_scatter_kv,
    "run": _op_run,
    "summary": _op_summary,
    "shutdown": _op_shutdown,
}


def _validate_protocol() -> None:
    """Import-time drift check: the dispatch table and the declared
    registry must cover exactly the same parent→child op set."""
    declared = {op for op, s in PROTOCOL.items()
                if s.direction == "parent_to_child"}
    handled = set(_OP_HANDLERS)
    if declared != handled:
        raise AssertionError(
            f"PROTOCOL/_OP_HANDLERS drift: declared-not-handled="
            f"{sorted(declared - handled)} handled-not-declared="
            f"{sorted(handled - declared)}")


_validate_protocol()


def _check_required(spec: ProtocolSpec,
                    header: Dict[str, Any]) -> None:
    missing = [f for f in spec.required if f not in header]
    if missing:
        raise ProtocolViolation(
            f"op {spec.op!r} frame is missing required header "
            f"field(s) {missing}")


def _worker_loop(conn: socket.socket, state: _WorkerState) -> None:
    from ..resilience.faults import InjectedFault

    while True:
        try:
            header, blobs = recv_frame(conn)
        except ReplicaDead:
            return                      # supervisor went away
        except FrameError as e:
            # undecodable header inside an honest length prefix: the
            # stream is still frame-aligned, so answer structurally
            # and keep serving — never tear the socket on a request
            # that merely failed to decode
            logger.warning("worker dropped malformed frame: %s", e)
            send_frame(conn, {"seq": None,
                              "error": type(e).__name__,
                              "message": str(e)[:500]})
            continue
        op = header.get("op")
        seq = header.get("seq")
        reply: Dict[str, Any] = {"seq": seq}
        rblobs: List[bytes] = []
        try:
            spec = PROTOCOL.get(op)
            if spec is None or spec.direction != "parent_to_child":
                raise ProtocolViolation(f"unknown op {op!r}")
            _check_required(spec, header)
            out, rblobs = _OP_HANDLERS[op](state, header, blobs)
            reply.update(out)
            if op == "shutdown":
                send_frame(conn, reply)
                return
        except (InjectedFault, KeyboardInterrupt, SystemExit):
            # an injected crash kills the PROCESS — that is the
            # drill.  The socket dies with us; the supervisor's
            # recv raises ReplicaDead and the restart path runs.
            raise
        except Exception as e:
            # request-level failures become an error REPLY, not a dead
            # child: the supervisor decides whether to retry or shed
            logger.warning("worker op %r failed: %s: %s",
                           op, type(e).__name__, e)
            reply = {"seq": seq, "error": type(e).__name__,
                     "message": str(e)[:500]}
            rblobs = []
        send_frame(conn, reply, rblobs)


def _worker_entry(spec_dict: Dict[str, Any],
                  socket_path: str) -> None:
    """Subprocess main.  Connects FIRST (so the parent's accept
    returns as soon as the interpreter is up), then builds the engine
    (jax import + warmup — the slow part the spawn timeout covers),
    then says hello and serves RPCs until shutdown or parent exit."""
    spec = EngineSpec.from_dict(spec_dict)
    # the connect deadline is the LISTENER's deadline (begin_spawn
    # stamps spawn_timeout_s into the spec) — one clock, two sides
    connect_timeout = (float(spec.connect_timeout_s)
                       if spec.connect_timeout_s is not None
                       else flag_float("APEX_TPU_CP_CONNECT_TIMEOUT_S"))
    conn = _connect_child(socket_path, timeout_s=connect_timeout)
    try:
        try:
            state = _build_worker(spec)
        except BaseException as e:
            with contextlib.suppress(Exception):
                send_frame(conn, {
                    "op": "hello", "rid": spec.replica_id,
                    "pid": os.getpid(),
                    "error": type(e).__name__,
                    "message": str(e)[:500]})
            raise
        try:
            send_frame(conn, {
                "op": "hello", "rid": spec.replica_id,
                "pid": os.getpid(), "replayed": state.replayed,
                "tick": state.engine.steps,
                "block_size": int(state.engine.cache_cfg.block_size)})
            _worker_loop(conn, state)
        finally:
            state.close()
    finally:
        with contextlib.suppress(Exception):
            conn.close()


# ---------------------------------------------------------------------------
# Parent side: one replica subprocess's handle
# ---------------------------------------------------------------------------

class ReplicaProcess:
    """Supervisor-side handle for one replica subprocess: spawn +
    hello handshake, sequenced RPCs with per-op timeout and bounded-
    backoff retry, SIGKILL + join for the reap, and the restart
    bookkeeping (incarnation counter, suspect-heartbeat count, restart
    budget).  Holds no engine — only the socket, the pid, and the
    :class:`EngineSpec` to respawn from."""

    def __init__(self, spec: EngineSpec, sock_dir: str, *,
                 max_restarts: int = 3,
                 spawn_timeout_s: float = 300.0,
                 backoff_base: float = 0.05,
                 backoff_max: float = 2.0,
                 rng: Optional[random.Random] = None):
        self.spec = spec
        self.sock_dir = sock_dir
        self.max_restarts = int(max_restarts)
        self.spawn_timeout_s = float(spawn_timeout_s)
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self._rng = rng or random.Random(0)
        self.proc = None
        self.conn: Optional[socket.socket] = None
        self.pid: Optional[int] = None
        self.incarnation = 0
        self.restarts = 0
        self.suspect = 0              # consecutive missed heartbeats
        self.stale = False            # last poll failed — score floor
        self.inflight = 0             # submits since the last fresh
        #                               snapshot (router reservation)
        self.last_snap: Optional[Dict[str, Any]] = None
        self.block_size: Optional[int] = None
        self.replayed_total = 0
        self.routable = True
        self.reaped = False
        self._seq = 0
        self._listener: Optional[socket.socket] = None
        self._sock_path: Optional[str] = None

    @property
    def replica_id(self) -> str:
        return self.spec.replica_id

    @property
    def role(self) -> str:
        return self.spec.role

    def alive(self) -> bool:
        return (self.proc is not None and self.proc.is_alive()
                and self.conn is not None)

    # -- spawn ----------------------------------------------------------

    def begin_spawn(self, *, replay: bool = False) -> None:
        """Phase 1: bind the listener and start the subprocess (the
        jax import + warmup runs concurrently across replicas;
        :meth:`finish_spawn` collects the hello)."""
        import multiprocessing as mp

        path = os.path.join(self.sock_dir,
                            f"{self.spec.replica_id}"
                            f".{self.incarnation}.sock")
        with contextlib.suppress(OSError):
            os.unlink(path)
        lst = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            lst.bind(path)
            lst.listen(1)
            lst.settimeout(self.spawn_timeout_s)
            spec = self._spawn_spec(replay)
            ctx = mp.get_context("spawn")
            self.proc = ctx.Process(
                target=_worker_entry, args=(spec.as_dict(), path),
                name=f"apex-replica-{self.spec.replica_id}",
                daemon=True)
            self.proc.start()
        except BaseException:
            # a failed bind/spawn must not leak the listener fd
            lst.close()
            raise
        self._listener = lst
        self._sock_path = path

    def _spawn_spec(self, replay: bool) -> EngineSpec:
        """The spec one incarnation actually receives: a
        respawn-for-replay strips the fault spec entirely (injected
        faults are once-per-serve by contract, and a fresh process's
        tick counter would otherwise re-reach K and re-fire forever
        — see faults.PROCESS_FATAL_KINDS), and the child's connect
        deadline is stamped with the listener's own
        ``spawn_timeout_s`` so both halves of the rendezvous run on
        one clock."""
        return dataclasses.replace(
            self.spec, replay=replay,
            fault=None if replay else self.spec.fault,
            connect_timeout_s=self.spawn_timeout_s)

    def finish_spawn(self) -> Dict[str, Any]:
        """Phase 2: accept + hello.  Raises :class:`RpcError` when
        the child failed to build (its hello carries the error)."""
        lst, path = self._listener, self._sock_path
        self._listener = self._sock_path = None
        try:
            try:
                conn, _ = lst.accept()
            except socket.timeout as e:
                raise RpcTimeout(
                    f"replica {self.replica_id} did not connect "
                    f"within {self.spawn_timeout_s}s") from e
        finally:
            lst.close()
            with contextlib.suppress(OSError):
                os.unlink(path)
        try:
            conn.settimeout(self.spawn_timeout_s)
            hello, _ = recv_frame(conn)
        except BaseException:
            # a child that died mid-hello must not leak the accepted
            # socket: close it, reap the corpse, then escalate
            conn.close()
            self.kill()
            raise
        if hello.get("error"):
            conn.close()
            self.kill()
            raise RpcError(
                f"replica {self.replica_id} failed to build: "
                f"{hello['error']}: {hello.get('message', '')}")
        self.conn = conn
        self.pid = int(hello["pid"])
        self.block_size = hello.get("block_size")
        self.incarnation += 1
        self.suspect = 0
        self.stale = False
        self.inflight = 0
        self.reaped = False
        replayed = int(hello.get("replayed", 0))
        self.replayed_total += replayed
        return hello

    def spawn(self, *, replay: bool = False) -> Dict[str, Any]:
        self.begin_spawn(replay=replay)
        return self.finish_spawn()

    # -- RPC ------------------------------------------------------------

    def post(self, op: str, header: Optional[Dict[str, Any]] = None,
             blobs: Sequence[bytes] = (), *,
             timeout: float) -> int:
        """Send one request without waiting (the freerun fan-out);
        returns the sequence number for :meth:`wait`."""
        spec = PROTOCOL.get(op)
        if spec is None or spec.direction != "parent_to_child":
            raise ProtocolViolation(
                f"op {op!r} is not a declared parent->child op")
        if blobs and not spec.request_blobs:
            raise ProtocolViolation(
                f"op {op!r} does not carry request blobs")
        _check_required(spec, header or {})
        if self.conn is None:
            raise ReplicaDead(f"replica {self.replica_id} has no "
                              f"connection")
        self._seq += 1
        frame = dict(header or {})
        frame["op"] = op
        frame["seq"] = self._seq
        self.conn.settimeout(timeout)
        send_frame(self.conn, frame, blobs)
        return self._seq

    def wait(self, seq: int, *, timeout: float
             ) -> Tuple[Dict[str, Any], List[bytes]]:
        """Collect the reply for ``seq``, draining stale replies from
        earlier timed-out calls (every reply echoes its seq, so a
        late answer can never be mistaken for the current one)."""
        if self.conn is None:
            raise ReplicaDead(f"replica {self.replica_id} has no "
                              f"connection")
        self.conn.settimeout(timeout)
        for _ in range(32):
            reply, rblobs = recv_frame(self.conn)
            if reply.get("seq") == seq:
                if "error" in reply:
                    raise RpcRemoteError(
                        f"replica {self.replica_id} op failed: "
                        f"{reply['error']}: "
                        f"{reply.get('message', '')}")
                return reply, rblobs
        raise RpcError(f"replica {self.replica_id}: no reply for "
                       f"seq {seq} after draining 32 stale frames")

    def call(self, op: str,
             header: Optional[Dict[str, Any]] = None,
             blobs: Sequence[bytes] = (), *, timeout: float,
             retries: int = 0
             ) -> Tuple[Dict[str, Any], List[bytes]]:
        """One RPC with explicit timeout and bounded-backoff retry.
        Retries re-SEND under a fresh seq — safe only for ops the
        registry marks idempotent, and refused otherwise: the
        callers escalate tick/submit/scatter to restart+replay
        instead, which the journal makes exactly-once."""
        spec = PROTOCOL.get(op)
        if retries and spec is not None and not spec.idempotent:
            raise ProtocolViolation(
                f"op {op!r} is not idempotent — it may not retry "
                f"in place (escalate to restart + journal replay)")
        last: Optional[RpcError] = None
        for attempt in range(int(retries) + 1):
            try:
                seq = self.post(op, header, blobs, timeout=timeout)
                return self.wait(seq, timeout=timeout)
            except RpcTimeout as e:
                last = e
                if attempt < retries:
                    time.sleep(backoff_delay(
                        attempt, base=self.backoff_base,
                        maximum=self.backoff_max, rng=self._rng))
                    continue
                raise
        raise last  # pragma: no cover — loop always returns/raises

    # -- reap -----------------------------------------------------------

    def kill(self, *, join_timeout_s: float = 10.0) -> None:
        """SIGKILL + join + close the socket.  Idempotent."""
        if self.proc is not None and self.proc.is_alive() \
                and self.proc.pid:
            with contextlib.suppress(OSError):
                os.kill(self.proc.pid, signal.SIGKILL)
        if self.proc is not None:
            self.proc.join(join_timeout_s)
        if self.conn is not None:
            with contextlib.suppress(Exception):
                self.conn.close()
            self.conn = None

    def shutdown(self, *, timeout_s: float = 10.0) -> bool:
        """Graceful stop: the shutdown RPC, then join.  Falls back to
        :meth:`kill` on any failure.  Returns True when the child
        exited on its own."""
        ok = False
        try:
            self.call("shutdown", timeout=timeout_s)
            if self.proc is not None:
                self.proc.join(timeout_s)
                ok = not self.proc.is_alive()
        except RpcError:
            ok = False
        self.kill()
        return ok


# ---------------------------------------------------------------------------
# The supervisor
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProcessFleetSummary:
    """What one process-fleet serve measured (the ``--serve-fleet
    --procs`` / bench-row source).  ``lost_requests`` is the
    accounting identity the whole design defends:
    ``offered - shed_admission - terminal`` MUST be 0 — every request
    the door admitted reached exactly one terminal state, across any
    number of kill-9s, torn handoffs, and scale events."""

    replicas: int
    prefill_replicas: int
    offered: int
    submitted: int               # reached an engine (offered - shed)
    shed_admission: int          # refused at the QoS door
    rejected: int                # engine-side admission rejects
    requests_done: int
    lost_requests: int
    tokens_generated: int
    wall_s: float
    tokens_per_sec: float
    rounds: int
    restarts: int
    rpc_timeouts: int
    handoffs: int
    handoff_blocks: int
    handoff_retries: int         # torn handoffs that went cold
    autoscale_ups: int
    autoscale_downs: int
    replayed_requests: int
    digest: str
    freerun: bool = False
    terminal_by_reason: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    per_replica: Dict[str, dict] = dataclasses.field(
        default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class _SupervisorLog:
    """The supervisor's event emitter: same ``event(kind, name,
    value=None, step=None, **attrs)`` shape as StepMonitor (so
    trace_check / monitor_summary read the merged JSONLs uniformly),
    backed by a JsonlSink plus an in-memory list for tests."""

    def __init__(self, jsonl_path: Optional[str] = None):
        self.events: List[Event] = []
        self._sink = JsonlSink(jsonl_path) if jsonl_path else None

    def event(self, kind: str, name: str, value=None,
              step: Optional[int] = None, **attrs) -> None:
        ev = Event(time=time.time(), step=step, kind=kind,
                   name=name, value=value, attrs=attrs)
        self.events.append(ev)
        if self._sink is not None:
            self._sink.emit(ev)

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()


@dataclasses.dataclass
class _Handoff:
    """One disaggregated prefill in flight: probe on the prefill
    replica, then gather → scatter → warm submit on a serve replica
    (any failure after the probe goes COLD, never lost)."""

    req: Dict[str, Any]
    probe_rid: str
    stage: str = "probe"          # probe -> ready


class ProcessFleet:
    """The supervising parent over N replica subprocesses.  See the
    module docstring for the architecture; construction takes the
    specs, the policies, and the fault plumbing — :meth:`start`
    spawns, :meth:`serve` drives, :meth:`close` reaps.  Usable as a
    context manager."""

    def __init__(self, specs: Sequence[EngineSpec], *,
                 jsonl_path: Optional[str] = None,
                 qos: Optional[QoSPolicy] = None,
                 autoscale: Optional[AutoscalePolicy] = None,
                 spec_factory: Optional[
                     Callable[[str, int], EngineSpec]] = None,
                 aggregator: Optional[FleetAggregator] = None,
                 exporter: Optional[MetricsExporter] = None,
                 metrics_port: Optional[int] = None,
                 fault: Optional[str] = None,
                 fault_replica: str = "r0",
                 max_restarts: int = 3,
                 backoff_base: float = 0.05,
                 backoff_max: float = 2.0,
                 rpc_timeout_s: Optional[float] = None,
                 poll_timeout_s: Optional[float] = None,
                 rpc_retries: Optional[int] = None,
                 spawn_timeout_s: Optional[float] = None,
                 heartbeat_misses: Optional[int] = None,
                 tick_seed: int = 0):
        if not specs:
            raise ValueError("a fleet needs at least one replica")
        ids = [s.replica_id for s in specs]
        if len(set(ids)) != len(ids):
            raise ValueError(f"duplicate replica ids: {ids}")
        self.log = _SupervisorLog(jsonl_path)
        self.qos = qos
        self.autoscale = autoscale
        self.spec_factory = spec_factory
        self.aggregator = aggregator or FleetAggregator()
        self.exporter = exporter
        self.metrics_port = metrics_port
        self.max_restarts = int(max_restarts)
        self.backoff_base = float(backoff_base)
        self.backoff_max = float(backoff_max)
        self.rpc_timeout_s = (float(rpc_timeout_s)
                              if rpc_timeout_s is not None else
                              flag_float("APEX_TPU_CP_RPC_TIMEOUT_S"))
        self.poll_timeout_s = (
            float(poll_timeout_s) if poll_timeout_s is not None
            else flag_float("APEX_TPU_CP_POLL_TIMEOUT_S"))
        self.rpc_retries = (int(rpc_retries)
                            if rpc_retries is not None else
                            flag_int("APEX_TPU_CP_RPC_RETRIES"))
        self.spawn_timeout_s = (
            float(spawn_timeout_s) if spawn_timeout_s is not None
            else flag_float("APEX_TPU_CP_SPAWN_TIMEOUT_S"))
        self.heartbeat_misses = (
            int(heartbeat_misses) if heartbeat_misses is not None
            else flag_int("APEX_TPU_CP_HEARTBEAT_MISSES"))
        self._rng = random.Random(20180 + int(tick_seed))
        child_fault, parent_fault = split_fault(fault)
        self._fault_replica = str(fault_replica)
        self._parent_fault = parse_fault(parent_fault)
        self._sock_dir: Optional[str] = None
        self._next_index = len(specs)
        self._metrics_server: Optional[MetricsServer] = None
        self._sigchld = threading.Event()
        self._prev_sigchld = None
        self.replicas: List[ReplicaProcess] = []
        self._specs = []
        base = int(metrics_port) if metrics_port else 0
        for i, spec in enumerate(specs):
            spec = dataclasses.replace(
                spec,
                fault=(child_fault
                       if spec.replica_id == self._fault_replica
                       else spec.fault),
                metrics_port=(spec.metrics_port
                              or (replica_metrics_port(base, i)
                                  if base else None)))
            self._specs.append(spec)
        # the supervisor's authoritative ledger
        self._routed: Dict[str, str] = {}       # rid -> replica_id
        self._terminal: Dict[str, str] = {}     # rid -> reason
        self._rows: Dict[str, List[int]] = {}   # rid -> out tokens
        self._class_open: Dict[str, set] = {}
        self._handoffs: Dict[str, _Handoff] = {}
        self.offered = 0
        self.shed_admission = 0
        self.rejected = 0
        self.restarts = 0
        self.rpc_timeouts = 0
        self.handoffs_done = 0
        self.handoff_blocks = 0
        self.handoff_retries = 0
        self.autoscale_ups = 0
        self.autoscale_downs = 0
        self._closed = False

    # -- lifecycle ------------------------------------------------------

    def __enter__(self) -> "ProcessFleet":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _on_sigchld(self, signum, frame) -> None:
        # APX803 flag-only discipline: a signal handler may only set
        # a flag the loop polls — the reap itself runs at a round
        # boundary, never in handler context
        self._sigchld.set()

    def start(self) -> None:
        """Spawn every replica (two-phase: all processes start, THEN
        all hellos are collected — the jax imports and warmups run
        concurrently), install the flag-only SIGCHLD handler, and
        bind the aggregated metrics server on the base port."""
        # AF_UNIX sun_path is ~108 bytes; pytest tmpdirs routinely
        # blow it, so the rendezvous sockets live under /tmp
        self._sock_dir = tempfile.mkdtemp(prefix="apexcp-")
        try:
            self._prev_sigchld = signal.signal(
                signal.SIGCHLD, self._on_sigchld)
        except ValueError:        # not the main thread — poll-only
            self._prev_sigchld = None
        for spec in self._specs:
            self.replicas.append(ReplicaProcess(
                spec, self._sock_dir,
                max_restarts=self.max_restarts,
                spawn_timeout_s=self.spawn_timeout_s,
                backoff_base=self.backoff_base,
                backoff_max=self.backoff_max, rng=self._rng))
        for rp in self.replicas:
            rp.begin_spawn()
        for rp in self.replicas:
            hello = rp.finish_spawn()
            self._emit_spawned(rp, hello)
        if self.metrics_port is not None:
            if self.exporter is None:
                self.exporter = MetricsExporter()
            self._metrics_server = MetricsServer(
                self.exporter, port=int(self.metrics_port),
                monitor=self.log)
            self._metrics_server.start()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for rp in self.replicas:
            if not rp.reaped:
                self._reap(rp, reason="shutdown", graceful=True)
        if self._metrics_server is not None:
            with contextlib.suppress(Exception):
                self._metrics_server.stop()
        if self._prev_sigchld is not None:
            with contextlib.suppress(ValueError):
                signal.signal(signal.SIGCHLD, self._prev_sigchld)
            self._prev_sigchld = None
        if self._sock_dir is not None:
            import shutil

            shutil.rmtree(self._sock_dir, ignore_errors=True)
            self._sock_dir = None
        self.log.close()

    # -- event helpers --------------------------------------------------

    def _emit_spawned(self, rp: ReplicaProcess,
                      hello: Dict[str, Any]) -> None:
        self.log.event("fleet", "replica_spawned",
                       replica=rp.replica_id, role=rp.role,
                       pid=rp.pid, incarnation=rp.incarnation,
                       replayed=int(hello.get("replayed", 0)))

    def _reap(self, rp: ReplicaProcess, *, reason: str,
              graceful: bool = False) -> None:
        """Reap ONE incarnation exactly once: (optionally graceful)
        stop, absorb the journal's terminals, emit the paired
        ``replica_reaped``."""
        if rp.reaped:
            return
        rp.reaped = True
        if graceful and rp.alive():
            rp.shutdown(timeout_s=min(10.0, self.rpc_timeout_s))
        else:
            rp.kill()
        self._absorb_journal(rp)
        self.log.event("fleet", "replica_reaped",
                       replica=rp.replica_id, pid=rp.pid,
                       incarnation=rp.incarnation, reason=reason)

    def _absorb_journal(self, rp: ReplicaProcess):
        """Fold the replica's on-disk ledger into the supervisor's:
        terminal records carry the full output token list, so
        requests that finished BEFORE a kill keep their tokens (and
        their digest rows) even though the fresh process never saw
        them.  Returns the JournalState (the submit-failure path
        checks ownership against it)."""
        path = rp.spec.journal_path
        if not path or not os.path.exists(path):
            return None
        state = RequestJournal.load(path)
        for rid, attrs in state.terminal.items():
            if str(rid).startswith(PREFILL_RID_PREFIX):
                continue
            self._record_terminal(
                str(rid), str(attrs.get("terminal", "finished")))
            self._rows.setdefault(
                str(rid),
                [int(t) for t in attrs.get("tokens", [])])
        return state

    def _record_terminal(self, rid: str, reason: str) -> None:
        if rid in self._terminal:
            return
        self._terminal[rid] = reason
        for open_set in self._class_open.values():
            open_set.discard(rid)

    def _mark_routed(self, rid: str, rp: ReplicaProcess,
                     cls: str) -> None:
        self._routed[rid] = rp.replica_id
        self._class_open.setdefault(cls, set()).add(rid)

    # -- per-op RPC policy, derived from the PROTOCOL registry ----------

    def _op_timeout(self, op: str) -> float:
        """The configured deadline for ``op``'s declared timeout
        class — call sites never carry their own float."""
        cls = PROTOCOL[op].timeout_class
        return {"rpc": self.rpc_timeout_s,
                "poll": self.poll_timeout_s,
                "spawn": self.spawn_timeout_s}[cls]

    def _op_retries(self, op: str) -> int:
        """The retry budget ``op`` is allowed: the configured budget
        when the registry marks it idempotent, zero otherwise (those
        escalate to restart + journal replay)."""
        return self.rpc_retries if PROTOCOL[op].idempotent else 0

    # -- restart (the heartbeat ⇒ SIGKILL ⇒ replay discipline) ----------

    def _restart(self, rp: ReplicaProcess, *, reason: str,
                 round_idx: int):
        """SIGKILL + bounded-backoff respawn + journal replay for one
        replica.  Returns the absorbed JournalState (None without a
        journal).  Raises :class:`FleetGiveUp` past the budget —
        bounded restarts, same contract as ``run_resumable``."""
        self.restarts += 1
        rp.restarts += 1
        self._reap(rp, reason=reason)
        state = (RequestJournal.load(rp.spec.journal_path)
                 if rp.spec.journal_path
                 and os.path.exists(rp.spec.journal_path) else None)
        if rp.restarts > rp.max_restarts:
            raise FleetGiveUp(
                f"replica {rp.replica_id} exhausted its restart "
                f"budget ({rp.max_restarts}); last reason: {reason}")
        delay = backoff_delay(rp.restarts - 1,
                              base=self.backoff_base,
                              maximum=self.backoff_max,
                              rng=self._rng)
        self.log.event("fleet", "replica_restart", step=round_idx,
                       replica=rp.replica_id, restarts=rp.restarts,
                       reason=reason, backoff_s=round(delay, 4))
        time.sleep(delay)
        hello = rp.spawn(replay=True)
        self._emit_spawned(rp, hello)
        rp.last_snap = None
        return state

    def _check_processes(self, round_idx: int) -> None:
        """The SIGCHLD flag's poll point (plus a liveness sweep — a
        child that died without a signal reaching us is still
        caught): every dead, unreaped replica restarts here."""
        self._sigchld.clear()
        for rp in list(self.replicas):
            if not rp.reaped and not rp.alive():
                self._restart(rp, reason="process_exit",
                              round_idx=round_idx)

    # -- gauge polls (heartbeats) ---------------------------------------

    def _poll_round(self, round_idx: int
                    ) -> Dict[str, Dict[str, Any]]:
        """One snapshot poll per replica.  A timeout (real or the
        ``rpc_timeout@K`` injector's dropped response) degrades the
        replica to its STALE snapshot and floors its router score —
        it never blocks the round.  ``heartbeat_misses`` consecutive
        misses ⇒ the replica is presumed wedged ⇒ SIGKILL + restart."""
        snaps: Dict[str, Dict[str, Any]] = {}
        for rp in list(self.replicas):
            if rp.reaped:
                continue
            inj = (self._parent_fault
                   if rp.replica_id == self._fault_replica else None)
            if inj is not None and inj.drop_rpc(round_idx):
                self.rpc_timeouts += 1
                rp.suspect += 1
                rp.stale = True
                self.log.event("fleet", "rpc_timeout",
                               step=round_idx,
                               replica=rp.replica_id, op="snapshot",
                               injected=True)
                if rp.last_snap is not None:
                    snaps[rp.replica_id] = rp.last_snap
                continue
            try:
                reply, _ = rp.call(
                    "snapshot",
                    timeout=self._op_timeout("snapshot"),
                    retries=self._op_retries("snapshot"))
                rp.last_snap = reply["snapshot"]
                rp.suspect = 0
                rp.stale = False
                rp.inflight = 0   # the fresh snapshot counts them
                snaps[rp.replica_id] = rp.last_snap
            except RpcTimeout:
                self.rpc_timeouts += 1
                rp.suspect += 1
                rp.stale = True
                self.log.event("fleet", "rpc_timeout",
                               step=round_idx,
                               replica=rp.replica_id, op="snapshot",
                               injected=False)
                if rp.suspect >= self.heartbeat_misses:
                    self._restart(rp, reason="missed_heartbeat",
                                  round_idx=round_idx)
                elif rp.last_snap is not None:
                    snaps[rp.replica_id] = rp.last_snap
            except (ReplicaDead, RpcRemoteError) as e:
                self._restart(
                    rp,
                    reason=f"poll_failed:{type(e).__name__}",
                    round_idx=round_idx)
        return snaps

    # -- routing --------------------------------------------------------

    def _serve_replicas(self) -> List[ReplicaProcess]:
        return [rp for rp in self.replicas
                if rp.role == "serve" and not rp.reaped]

    def _prefill_replica(self) -> Optional[ReplicaProcess]:
        for rp in self.replicas:
            if rp.role == "prefill" and not rp.reaped:
                return rp
        return None

    @staticmethod
    def _warm_keys(prompt: List[int], block_size: Optional[int]
                   ) -> List[str]:
        """The prompt's chain keys (hex), for sticky warm routing
        against each snapshot's ``warm_prefix_keys``.  Lazy import —
        the hashing itself is pure host code."""
        if not block_size:
            return []
        try:
            from .kv_cache import prefix_chain_keys

            return [k.hex() for k in
                    prefix_chain_keys(prompt, int(block_size))]
        except Exception:  # apex-lint: disable=APX202 -- warm-key hashing is best-effort routing affinity; any failure degrades to cold routing, never fails the submit
            return []

    def _route(self, req: Dict[str, Any]
               ) -> Optional[ReplicaProcess]:
        """Best serve replica for one request: fresh-over-stale,
        unshedded-over-shedding, warm-over-cold, then pool headroom
        and backlog — the FleetRouter scoring over RPC'd snapshots.
        A stale (timed-out) poll floors the score instead of
        excluding the replica: degraded, never stalled."""
        best = None
        best_score = None
        for rp in self._serve_replicas():
            if not rp.routable:
                continue
            snap = rp.last_snap or {}
            warm = 0
            keys = self._warm_keys(req["prompt"], rp.block_size)
            if keys:
                snap_keys = set(snap.get("warm_prefix_keys", []))
                if snap_keys.intersection(keys):
                    warm = 1
            headroom = (int(snap.get("available_blocks", 0))
                        - int(snap.get("reserved_blocks", 0)))
            # inflight = submits the snapshot predates — without the
            # reservation term one admission round dumps EVERY pending
            # request on the round-start-emptiest replica
            backlog = (int(snap.get("queue_depth", 0))
                       + int(snap.get("prefilling", 0))
                       + int(snap.get("active", 0))
                       + rp.inflight)
            score = (0 if (rp.stale or snap == {}) else 1,
                     0 if snap.get("shed_engaged") else 1,
                     warm, headroom, -backlog, rp.replica_id)
            if best_score is None or score > best_score:
                best, best_score = rp, score
        return best

    def _submit(self, rp: ReplicaProcess, req: Dict[str, Any],
                cls: str, round_idx: int, *,
                track: bool = True) -> bool:
        """Submit one request, surviving a replica death mid-submit:
        after the restart, the journal says whether the dead
        incarnation journaled the submit (⇒ the replay owns it) or
        never saw it (⇒ re-route).  Never double-submits, never
        drops."""
        rid = str(req["rid"])
        for _ in range(self.max_restarts + 2):
            try:
                rp.call("submit", {"req": req},
                        timeout=self._op_timeout("submit"))
                rp.inflight += 1
                if track:
                    self._mark_routed(rid, rp, cls)
                return True
            except RpcRemoteError as e:
                self.rejected += 1
                if track:
                    self._record_terminal(rid, "rejected")
                self.log.event("fleet", "request_rejected",
                               step=round_idx, rid=rid,
                               replica=rp.replica_id,
                               error=str(e)[:200])
                return False
            except RpcError as e:
                state = self._restart(
                    rp,
                    reason=f"submit_failed:{type(e).__name__}",
                    round_idx=round_idx)
                if state is not None and rid in state.submitted \
                        and rid not in state.terminal:
                    # the dead incarnation journaled it — the replay
                    # just re-entered it; it is routed, not lost
                    if track:
                        self._mark_routed(rid, rp, cls)
                    return True
                nxt = self._route(req)
                if nxt is None:
                    continue
                rp = nxt
        raise FleetGiveUp(f"could not place request {rid}")

    # -- QoS admission + disaggregated handoff --------------------------

    def _burning(self) -> List[str]:
        out: set = set()
        for rp in self.replicas:
            if rp.last_snap:
                out.update(rp.last_snap.get("slo_burning", []))
        return sorted(out)

    def _admit(self, pending: deque, round_idx: int) -> None:
        pf = self._prefill_replica()
        while pending:
            req = pending[0]
            cls = QoSPolicy.class_of(req.get("priority"))
            if self.qos is not None:
                open_count = len(self._class_open.get(cls, ()))
                ok, why = self.qos.admit(cls, open_count,
                                         self._burning())
                if not ok:
                    pending.popleft()
                    self.shed_admission += 1
                    self.log.event(
                        "fleet", "request_shed_admission",
                        step=round_idx, rid=str(req["rid"]),
                        priority_class=cls, reason=why)
                    continue
            if pf is not None and pf.block_size \
                    and len(req["prompt"]) >= int(pf.block_size):
                probe_rid = f"{PREFILL_RID_PREFIX}{req['rid']}"
                probe = dict(req, rid=probe_rid, max_new_tokens=1,
                             deadline_ms=None)
                pending.popleft()
                # probes are untracked plumbing — the real rid is
                # owned by the handoff until its warm/cold submit
                if self._submit(pf, probe, cls, round_idx,
                                track=False):
                    self._handoffs[probe_rid] = _Handoff(
                        req=req, probe_rid=probe_rid)
                else:
                    # probe rejected — admit the real request cold
                    self._submit_cold(req, cls, round_idx,
                                      stage="probe_rejected")
                continue
            rp = self._route(req)
            if rp is None:
                return            # nothing routable — retry next round
            pending.popleft()
            self._submit(rp, req, cls, round_idx)

    def _submit_cold(self, req: Dict[str, Any], cls: str,
                     round_idx: int, *, stage: str) -> None:
        """The torn-handoff fallback: the request admits cold on the
        best serve replica.  Degraded (no warm pages), never lost."""
        self.handoff_retries += 1
        self.log.event("fleet", "kv_handoff_retry", step=round_idx,
                       rid=str(req["rid"]), stage=stage)
        rp = self._route(req)
        if rp is None:
            rp = next(iter(self._serve_replicas()), None)
        if rp is None:
            raise FleetGiveUp("no serve replica for cold fallback")
        self._submit(rp, req, cls, round_idx)

    def _advance_handoffs(self, round_idx: int) -> None:
        """Drive every finished probe through gather → scatter →
        warm submit.  EVERY rpc failure in the chain — timeout, dead
        replica, payload mismatch — lands in :meth:`_submit_cold`."""
        ready = [h for h in self._handoffs.values()
                 if h.stage == "ready"]
        for h in ready:
            del self._handoffs[h.probe_rid]
            cls = QoSPolicy.class_of(h.req.get("priority"))
            pf = self._prefill_replica()
            if pf is None:
                self._submit_cold(h.req, cls, round_idx,
                                  stage="prefill_gone")
                continue
            try:
                reply, blobs = pf.call(
                    "gather_kv", {"prompt": h.req["prompt"]},
                    timeout=self._op_timeout("gather_kv"),
                    retries=self._op_retries("gather_kv"))
            except RpcError:
                self._submit_cold(h.req, cls, round_idx,
                                  stage="gather")
                continue
            n = int(reply.get("resident", -1))
            if n <= 0:
                self._submit_cold(h.req, cls, round_idx,
                                  stage="not_resident")
                continue
            dst = self._route(h.req)
            if dst is None:
                self._submit_cold(h.req, cls, round_idx,
                                  stage="no_dst")
                continue
            try:
                # a literal header — the auditor checks these keys
                # against PROTOCOL["scatter_kv"] field for field
                scatter = {"names": reply["names"],
                           "shapes": reply["shapes"],
                           "dtypes": reply["dtypes"],
                           "geometry": reply["geometry"],
                           "prompt": h.req["prompt"], "n": n}
                dst.call("scatter_kv", scatter, blobs,
                         timeout=self._op_timeout("scatter_kv"))
            except RpcError:
                self._submit_cold(h.req, cls, round_idx,
                                  stage="scatter")
                continue
            self.handoffs_done += 1
            self.handoff_blocks += n
            self.log.event("fleet", "kv_handoff", value=n,
                           step=round_idx, pages=n,
                           rid=str(h.req["rid"]),
                           src=pf.replica_id, dst=dst.replica_id)
            self._submit(dst, h.req, cls, round_idx)

    # -- the tick round -------------------------------------------------

    def _tick_round(self, round_idx: int) -> bool:
        """Tick every live replica once, in a seed-permuted order
        (the PR 15 schedule-stress surface: the fleet digest must not
        care).  Any tick failure escalates to restart+replay — a tick
        is not idempotent, so it never retries in place."""
        order = list(self.replicas)
        self._rng.shuffle(order)
        busy = False
        for rp in order:
            if rp.reaped:
                continue
            try:
                reply, _ = rp.call("tick",
                                   timeout=self._op_timeout("tick"))
            except RpcError as e:
                self._restart(
                    rp, reason=f"tick_failed:{type(e).__name__}",
                    round_idx=round_idx)
                busy = True       # the replay re-entered its work
                continue
            busy = busy or bool(reply.get("busy"))
            for rid, reason in reply.get("finished", []):
                if str(rid).startswith(PREFILL_RID_PREFIX):
                    h = self._handoffs.get(str(rid))
                    if h is not None and h.stage == "probe":
                        h.stage = "ready"
                    continue
                self._record_terminal(str(rid), str(reason))
        return busy

    # -- observe / autoscale --------------------------------------------

    def _observe(self, round_idx: int,
                 snaps: Dict[str, Dict[str, Any]]) -> None:
        if not snaps:
            return
        attrs = self.aggregator.observe(round_idx, snaps)
        self.log.event("fleet_tick", "fleet_tick",
                       value=attrs.get("queue_depth"),
                       step=round_idx, **attrs)
        if self.exporter is not None:
            try:
                self.exporter.publish(self._registry(snaps),
                                      tick=round_idx)
            except Exception as e:
                logger.warning("fleet exporter publish failed: %s",
                               str(e)[:160])

    def _registry(self, snaps: Dict[str, Dict[str, Any]]
                  ) -> MetricsRegistry:
        """The aggregated fleet view the BASE metrics port serves
        (each replica's own exporter lives in its subprocess on
        ``base + 1 + k``)."""
        reg = MetricsRegistry()
        reg.gauge("apex_tpu_fleet_replicas",
                  "Serve-role replica subprocesses."
                  ).set(len(self._serve_replicas()))
        reg.gauge("apex_tpu_fleet_restarts",
                  "Replica subprocess restarts (supervisor)."
                  ).set(self.restarts)
        reg.gauge("apex_tpu_fleet_rpc_timeouts",
                  "Timed-out control-plane RPCs."
                  ).set(self.rpc_timeouts)
        qd = reg.gauge("apex_tpu_replica_queue_depth",
                       "Per-replica queue depth (gauge poll).")
        tok = reg.gauge("apex_tpu_replica_tokens_generated",
                        "Per-replica generated tokens (gauge poll).")
        for rid, snap in sorted(snaps.items()):
            qd.set(int(snap.get("queue_depth", 0)), replica=rid)
            tok.set(int(snap.get("tokens_generated", 0)),
                    replica=rid)
        return reg

    def _autoscale_round(self, round_idx: int,
                         snaps: Dict[str, Dict[str, Any]]) -> None:
        if self.autoscale is None:
            return
        backlog = sum(int(s.get("queue_depth", 0))
                      + int(s.get("prefilling", 0))
                      + int(s.get("active", 0))
                      for s in snaps.values())
        action = self.autoscale.decide(
            round_idx, len(self._serve_replicas()), backlog,
            self.aggregator.trends())
        if action == "up":
            self._scale_up(round_idx, backlog)
        elif action == "down":
            self._scale_down(round_idx, backlog)

    def _scale_up(self, round_idx: int, backlog: int) -> None:
        if self.spec_factory is None:
            logger.warning("autoscale up skipped: no spec_factory")
            return
        idx = self._next_index
        self._next_index += 1
        spec = self.spec_factory(f"r{idx}", idx)
        rp = ReplicaProcess(spec, self._sock_dir,
                            max_restarts=self.max_restarts,
                            spawn_timeout_s=self.spawn_timeout_s,
                            backoff_base=self.backoff_base,
                            backoff_max=self.backoff_max,
                            rng=self._rng)
        hello = rp.spawn()
        self.replicas.append(rp)
        self._emit_spawned(rp, hello)
        self.autoscale_ups += 1
        self.log.event("fleet", "autoscale", step=round_idx,
                       action="up", reason="backlog_trend",
                       replica=rp.replica_id, backlog=backlog,
                       replicas=len(self._serve_replicas()))

    def _scale_down(self, round_idx: int, backlog: int) -> None:
        """Drain-then-reap: admit-stop the emptiest serve replica;
        the reap happens in :meth:`_maybe_reap_draining` once its
        open requests finish — zero lost, the swap_weights
        contract."""
        victims = [rp for rp in self._serve_replicas()
                   if rp.routable]
        if len(victims) <= (self.autoscale.min_replicas
                            if self.autoscale else 1):
            return
        victim = min(victims, key=lambda rp: (
            sum(1 for rid, owner in self._routed.items()
                if owner == rp.replica_id
                and rid not in self._terminal),
            rp.replica_id))
        victim.routable = False
        self.autoscale_downs += 1
        self.log.event("fleet", "autoscale", step=round_idx,
                       action="down", reason="idle_trend",
                       replica=victim.replica_id, backlog=backlog,
                       replicas=len(self._serve_replicas()) - 1)

    def _maybe_reap_draining(self, round_idx: int) -> None:
        for rp in list(self.replicas):
            if rp.routable or rp.reaped or rp.role != "serve":
                continue
            open_rids = [rid for rid, owner in self._routed.items()
                         if owner == rp.replica_id
                         and rid not in self._terminal]
            if open_rids:
                continue
            self._reap(rp, reason="scale_down", graceful=True)
            self.replicas.remove(rp)

    # -- the serve loops ------------------------------------------------

    @staticmethod
    def _req_dict(r) -> Dict[str, Any]:
        """Accept engine Requests OR plain dicts (the parent never
        imports the engine class)."""
        if isinstance(r, dict):
            d = dict(r)
        else:
            d = {k: getattr(r, k, None)
                 for k in ("rid", "prompt", "max_new_tokens",
                           "eos_token", "deadline_ms", "priority")}
        d["rid"] = str(d["rid"])
        d["prompt"] = [int(t) for t in d["prompt"]]
        d["max_new_tokens"] = int(d.get("max_new_tokens") or 1)
        return d

    def serve(self, requests: Sequence[Any], *,
              freerun: bool = False,
              max_rounds: int = 100000) -> ProcessFleetSummary:
        """Drive the fleet over ``requests`` to completion.  The
        default stepped loop supervises round by round (polls, QoS
        admission, handoffs, ticks, heartbeats, aggregation,
        autoscale); ``freerun`` submits everything up front and lets
        every subprocess decode concurrently under one ``run`` RPC —
        the scaling mode (no autoscale/QoS/parent-fault support
        there)."""
        reqs = [self._req_dict(r) for r in requests]
        self.offered += len(reqs)
        t0 = time.perf_counter()
        if freerun:
            if self.autoscale is not None or self.qos is not None \
                    or self._parent_fault is not None:
                raise ValueError(
                    "freerun supports neither autoscale, QoS, nor "
                    "parent-side fault injection — use the stepped "
                    "loop")
            rounds = self._serve_freerun(reqs)
        else:
            rounds = self._serve_stepped(reqs, max_rounds)
        wall = time.perf_counter() - t0
        return self._summarize(rounds, wall, freerun=freerun)

    def _serve_stepped(self, reqs: List[Dict[str, Any]],
                       max_rounds: int) -> int:
        pending = deque(reqs)
        rounds = 0
        while True:
            rounds += 1
            if rounds > max_rounds:
                raise RuntimeError(
                    f"fleet did not drain within {max_rounds} "
                    f"rounds: {len(pending)} pending, "
                    f"{len(self._handoffs)} handoff(s) in flight")
            self._check_processes(rounds)
            snaps = self._poll_round(rounds)
            self._admit(pending, rounds)
            self._advance_handoffs(rounds)
            busy = self._tick_round(rounds)
            self._observe(rounds, snaps)
            self._autoscale_round(rounds, snaps)
            self._maybe_reap_draining(rounds)
            open_left = any(rid not in self._terminal
                            for rid in self._routed)
            if not pending and not self._handoffs \
                    and not open_left and not busy:
                return rounds

    def _serve_freerun(self, reqs: List[Dict[str, Any]]) -> int:
        serve_rps = self._serve_replicas()
        if not serve_rps:
            raise FleetGiveUp("no serve replicas")
        for i, req in enumerate(reqs):
            rp = serve_rps[i % len(serve_rps)]
            self._submit(rp, req,
                         QoSPolicy.class_of(req.get("priority")),
                         round_idx=0)
        pending_seq: Dict[str, int] = {}
        for rp in self.replicas:
            if not rp.reaped:
                pending_seq[rp.replica_id] = rp.post(
                    "run", timeout=self._op_timeout("run"))
        for rp in list(self.replicas):
            if rp.reaped:
                continue
            for attempt in range(self.max_restarts + 1):
                try:
                    reply, _ = rp.wait(
                        pending_seq[rp.replica_id],
                        timeout=self._op_timeout("run"))
                    for rid, reason in reply.get("finished", []):
                        if not str(rid).startswith(
                                PREFILL_RID_PREFIX):
                            self._record_terminal(str(rid),
                                                  str(reason))
                    break
                except RpcError as e:
                    self._restart(
                        rp,
                        reason=f"run_failed:{type(e).__name__}",
                        round_idx=attempt)
                    pending_seq[rp.replica_id] = rp.post(
                        "run", timeout=self._op_timeout("run"))
        snaps = self._poll_round(1)
        self._observe(1, snaps)
        return 1

    # -- the verdict ----------------------------------------------------

    def fleet_rows(self) -> Dict[str, List[int]]:
        """The merged ``{rid: tokens}`` ledger: live engines' rows
        (over RPC) layered over journal-absorbed terminals.  The
        digest over these is the cross-run identity proof."""
        rows = dict(self._rows)
        for rp in self.replicas:
            if rp.reaped or not rp.alive():
                continue
            try:
                reply, _ = rp.call(
                    "summary",
                    timeout=self._op_timeout("summary"),
                    retries=self._op_retries("summary"))
            except RpcError:
                continue
            for rid, toks in reply.get("rows", {}).items():
                if not str(rid).startswith(PREFILL_RID_PREFIX):
                    rows[str(rid)] = [int(t) for t in toks]
        return rows

    def _summarize(self, rounds: int, wall: float, *,
                   freerun: bool) -> ProcessFleetSummary:
        per_replica: Dict[str, dict] = {}
        rows = dict(self._rows)
        for rp in self.replicas:
            if rp.reaped or not rp.alive():
                continue
            try:
                reply, _ = rp.call(
                    "summary",
                    timeout=self._op_timeout("summary"),
                    retries=self._op_retries("summary"))
            except RpcError:
                continue
            per_replica[rp.replica_id] = reply.get("summary", {})
            for rid, toks in reply.get("rows", {}).items():
                if not str(rid).startswith(PREFILL_RID_PREFIX):
                    rows[str(rid)] = [int(t) for t in toks]
        for rp in self.replicas:
            self._absorb_journal(rp)
            rows.update({rid: t for rid, t in self._rows.items()
                         if rid not in rows})
        by_reason: Dict[str, int] = {}
        for reason in self._terminal.values():
            by_reason[reason] = by_reason.get(reason, 0) + 1
        tokens = sum(len(t) for t in rows.values())
        done = sum(1 for rid in self._routed
                   if self._terminal.get(rid) == "finished")
        # Rejected requests are terminal-but-never-routed, so count the
        # terminal ledger directly: every offered request must end up
        # either shed at the door or with a terminal record.
        lost = (self.offered - self.shed_admission
                - len(self._terminal))
        digest = fleet_rows_digest(rows)
        summary = ProcessFleetSummary(
            replicas=len(self._serve_replicas()),
            prefill_replicas=sum(
                1 for rp in self.replicas
                if rp.role == "prefill" and not rp.reaped),
            offered=self.offered,
            submitted=len(self._routed),
            shed_admission=self.shed_admission,
            rejected=self.rejected,
            requests_done=done,
            lost_requests=lost,
            tokens_generated=tokens,
            wall_s=wall,
            tokens_per_sec=(tokens / wall if wall > 0 else 0.0),
            rounds=rounds,
            restarts=self.restarts,
            rpc_timeouts=self.rpc_timeouts,
            handoffs=self.handoffs_done,
            handoff_blocks=self.handoff_blocks,
            handoff_retries=self.handoff_retries,
            autoscale_ups=self.autoscale_ups,
            autoscale_downs=self.autoscale_downs,
            replayed_requests=sum(rp.replayed_total
                                  for rp in self.replicas),
            digest=digest,
            freerun=freerun,
            terminal_by_reason=by_reason,
            per_replica=per_replica)
        self.log.event("fleet", "fleet_done",
                       value=summary.tokens_per_sec,
                       **{k: v for k, v in summary.as_dict().items()
                          if k not in ("per_replica",
                                       "terminal_by_reason")})
        return summary
