"""Process-isolated control-plane tests (ISSUE-18): the socket wire
protocol, the EngineSpec recipe, the routing-invariant fleet digest,
the autoscale + QoS policies, the ``kill9``/``rpc_timeout`` fault
kinds, the per-replica metrics-port layout (and the MetricsServer
port-collision regression it replaces), the supervisor-trace pairing
checks, and the monitor-summary control-plane digest.

The heavy end-to-end drills — kill-9 + journal replay across a real
process boundary, rpc_timeout no-stall, the tick-seed process sweep —
spawn real subprocesses (each ~15 s of jax import + warmup on CPU)
and are marked ``slow``; ci.sh step 16 runs the kill-9 drill on every
push regardless.
"""
import json
import socket
import struct

import pytest

from apex_tpu.monitor.events import Event
from apex_tpu.monitor.export import (MetricsExporter, MetricsServer,
                                     replica_metrics_port)
from apex_tpu.monitor.summary import render, summarize
from apex_tpu.monitor.tracing import check_serve_trace
from apex_tpu.resilience.faults import (PARENT_KINDS,
                                        PROCESS_FATAL_KINDS,
                                        parse_fault, split_fault)
from apex_tpu.serving import (AutoscalePolicy, EngineSpec, QoSClass,
                              QoSPolicy, ReplicaDead, RpcError,
                              RpcTimeout, fleet_rows_digest,
                              recv_frame, send_frame)
from apex_tpu.serving import control_plane as cp
from apex_tpu.serving.control_plane import (FrameError, PROTOCOL,
                                            ProcessFleet,
                                            ProtocolSpec,
                                            ProtocolViolation,
                                            ReplicaProcess)
from apex_tpu.serving.resilience import ShedPolicy


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------

class TestWireProtocol:
    def test_round_trip_header_and_blobs(self):
        a, b = socket.socketpair()
        try:
            blobs = [b"\x00\x01rawbytes", b"", b"x" * 4096]
            send_frame(a, {"op": "scatter_kv", "seq": 7,
                           "pages": [1, 2]}, blobs)
            header, got = recv_frame(b)
            assert header["op"] == "scatter_kv"
            assert header["seq"] == 7
            assert header["pages"] == [1, 2]
            assert got == blobs
        finally:
            a.close()
            b.close()

    def test_round_trip_no_blobs(self):
        a, b = socket.socketpair()
        try:
            send_frame(a, {"op": "tick", "seq": 1})
            header, got = recv_frame(b)
            assert header == {"op": "tick", "seq": 1}
            assert got == []
        finally:
            a.close()
            b.close()

    def test_recv_timeout_raises_rpc_timeout(self):
        a, b = socket.socketpair()
        try:
            b.settimeout(0.05)
            with pytest.raises(RpcTimeout):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_peer_close_raises_replica_dead(self):
        a, b = socket.socketpair()
        a.close()
        try:
            with pytest.raises(ReplicaDead):
                recv_frame(b)
        finally:
            b.close()

    def test_torn_frame_raises_replica_dead(self):
        # length prefix promises more bytes than the peer delivers
        # before closing: the mid-frame EOF must surface as
        # ReplicaDead (the supervisor's restart signal), not hang
        a, b = socket.socketpair()
        a.sendall(struct.pack(">I", 64) + b'{"op":')
        a.close()
        try:
            with pytest.raises(ReplicaDead):
                recv_frame(b)
        finally:
            b.close()

    def test_corrupt_length_prefix_raises_rpc_error(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack(">I", 1 << 30))
            with pytest.raises(RpcError):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_malformed_header_json_raises_rpc_error(self):
        a, b = socket.socketpair()
        try:
            payload = b"not json at all"
            a.sendall(struct.pack(">I", len(payload)) + payload)
            with pytest.raises(RpcError):
                recv_frame(b)
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# adversarial frames (ISSUE-20 satellite: every malformed input maps
# to the right taxonomy error — never a hang or a raw OSError)
# ---------------------------------------------------------------------------

class TestAdversarialFrames:
    def _pair(self):
        a, b = socket.socketpair()
        b.settimeout(0.5)              # any stall surfaces as RpcTimeout
        return a, b

    def _raw(self, a, payload):
        a.sendall(struct.pack(">I", len(payload)) + payload)

    def test_truncated_length_prefix(self):
        a, b = self._pair()
        try:
            a.sendall(b"\x00\x00")     # 2 of the 4 prefix bytes
            a.close()
            with pytest.raises(ReplicaDead):
                recv_frame(b)
        finally:
            b.close()

    def test_oversized_declared_blob_length(self):
        a, b = self._pair()
        try:
            self._raw(a, json.dumps(
                {"op": "x", "blobs": [cp.MAX_BLOB_BYTES + 1]}
            ).encode())
            with pytest.raises(RpcError):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_negative_blob_length(self):
        a, b = self._pair()
        try:
            self._raw(a, b'{"op": "x", "blobs": [-1]}')
            with pytest.raises(RpcError):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_non_list_blob_lengths(self):
        a, b = self._pair()
        try:
            self._raw(a, b'{"op": "x", "blobs": 5}')
            with pytest.raises(RpcError):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_blob_count_mismatch_times_out_not_hangs(self):
        # header promises 5 blob bytes the sender never delivers:
        # the bounded recv must surface RpcTimeout, not block forever
        a, b = self._pair()
        try:
            self._raw(a, b'{"op": "x", "seq": 1, "blobs": [5]}')
            with pytest.raises(RpcTimeout):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_junk_json_is_frame_error(self):
        # honest prefix + undecodable header: the stream stays
        # frame-aligned, so this is the RECOVERABLE class
        a, b = self._pair()
        try:
            self._raw(a, b"not json at all")
            with pytest.raises(FrameError):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_non_object_json_header_is_frame_error(self):
        a, b = self._pair()
        try:
            self._raw(a, b"[1, 2, 3]")
            with pytest.raises(FrameError):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_frame_error_is_rpc_error(self):
        # parent-side callers that catch RpcError keep working
        assert issubclass(FrameError, RpcError)
        assert issubclass(ProtocolViolation, RpcError)


# ---------------------------------------------------------------------------
# worker-loop resilience (ISSUE-20 satellite: decodable-but-invalid
# requests get a structured error reply and the loop stays alive)
# ---------------------------------------------------------------------------

class _StubWorkerState:
    fault = None


class TestWorkerLoopResilience:
    def _start_worker(self):
        import threading

        a, b = socket.socketpair()
        a.settimeout(5.0)
        t = threading.Thread(target=cp._worker_loop,
                             args=(b, _StubWorkerState()),
                             daemon=True)
        t.start()
        return a, b, t

    def test_malformed_then_invalid_then_served(self):
        a, b, t = self._start_worker()
        try:
            # 1) undecodable header: structured error, loop alive
            payload = b"{this is not json"
            a.sendall(struct.pack(">I", len(payload)) + payload)
            reply, _ = recv_frame(a)
            assert reply["seq"] is None
            assert reply["error"] == "FrameError"
            # 2) unknown op: structured error, loop alive
            send_frame(a, {"op": "bogus", "seq": 1})
            reply, _ = recv_frame(a)
            assert reply["seq"] == 1
            assert reply["error"] == "ProtocolViolation"
            assert "unknown op" in reply["message"]
            # 3) declared op missing a required field: same contract
            send_frame(a, {"op": "submit", "seq": 2})
            reply, _ = recv_frame(a)
            assert reply["seq"] == 2
            assert reply["error"] == "ProtocolViolation"
            assert "req" in reply["message"]
            # 4) a child->parent op on the wrong side is refused too
            send_frame(a, {"op": "hello", "seq": 3})
            reply, _ = recv_frame(a)
            assert reply["seq"] == 3
            assert reply["error"] == "ProtocolViolation"
            # 5) the SAME socket still serves a valid op afterwards
            send_frame(a, {"op": "shutdown", "seq": 4})
            reply, _ = recv_frame(a)
            assert reply == {"seq": 4}
            t.join(5.0)
            assert not t.is_alive()
        finally:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# the protocol registry (protocol-as-data: both runtime sides derive
# from PROTOCOL, and drift fails at import)
# ---------------------------------------------------------------------------

class TestProtocolRegistry:
    def test_dispatch_covers_registry_exactly(self):
        declared = {op for op, s in PROTOCOL.items()
                    if s.direction == "parent_to_child"}
        assert declared == set(cp._OP_HANDLERS)
        cp._validate_protocol()        # idempotent re-check

    def test_hello_is_child_to_parent(self):
        assert PROTOCOL["hello"].direction == "child_to_parent"
        assert "rid" in PROTOCOL["hello"].required
        assert "pid" in PROTOCOL["hello"].required

    def test_spec_validates_direction_and_timeout_class(self):
        with pytest.raises(ValueError):
            ProtocolSpec("x", direction="sideways")
        with pytest.raises(ValueError):
            ProtocolSpec("x", timeout_class="eventually")
        with pytest.raises(ValueError):
            ProtocolSpec("x", required=("seq",))   # frame-layer field

    def test_post_refuses_undeclared_op(self):
        rp = ReplicaProcess(EngineSpec(replica_id="r0"), "/tmp")
        with pytest.raises(ProtocolViolation):
            rp.post("bogus", timeout=1.0)

    def test_post_refuses_blobs_on_blobless_op(self):
        rp = ReplicaProcess(EngineSpec(replica_id="r0"), "/tmp")
        with pytest.raises(ProtocolViolation):
            rp.post("tick", None, [b"x"], timeout=1.0)

    def test_post_refuses_missing_required_field(self):
        rp = ReplicaProcess(EngineSpec(replica_id="r0"), "/tmp")
        with pytest.raises(ProtocolViolation):
            rp.post("submit", {}, timeout=1.0)

    def test_call_refuses_retry_on_non_idempotent_op(self):
        rp = ReplicaProcess(EngineSpec(replica_id="r0"), "/tmp")
        assert not PROTOCOL["submit"].idempotent
        with pytest.raises(ProtocolViolation):
            rp.call("submit", {"req": {}}, timeout=1.0, retries=1)

    def test_fleet_per_op_policy_derives_from_registry(self):
        fleet = ProcessFleet([EngineSpec(replica_id="r0")],
                             rpc_timeout_s=7.0, poll_timeout_s=3.0,
                             spawn_timeout_s=11.0, rpc_retries=2)
        assert fleet._op_timeout("snapshot") == 3.0   # poll class
        assert fleet._op_timeout("submit") == 7.0     # rpc class
        assert fleet._op_timeout("run") == 11.0       # spawn class
        assert fleet._op_retries("snapshot") == 2     # idempotent
        assert fleet._op_retries("submit") == 0       # escalates
        assert fleet._op_retries("scatter_kv") == 0   # escalates

    def test_spawn_spec_stamps_connect_timeout(self):
        # one clock, two sides: the child's connect deadline IS the
        # listener's spawn deadline (the 30s-vs-300s race fix)
        rp = ReplicaProcess(EngineSpec(replica_id="r0"), "/tmp",
                            spawn_timeout_s=123.0)
        spec = rp._spawn_spec(False)
        assert spec.connect_timeout_s == 123.0
        assert spec.replay is False

    def test_spawn_spec_replay_strips_fault(self):
        rp = ReplicaProcess(
            EngineSpec(replica_id="r0", fault="kill9@2"), "/tmp",
            spawn_timeout_s=9.0)
        spec = rp._spawn_spec(True)
        assert spec.replay is True and spec.fault is None
        assert spec.connect_timeout_s == 9.0
        # the first spawn keeps the fault (the drill must fire once)
        assert rp._spawn_spec(False).fault == "kill9@2"

    def test_engine_spec_round_trips_connect_timeout(self):
        spec = EngineSpec(replica_id="r0", connect_timeout_s=42.0)
        assert EngineSpec.from_dict(
            spec.as_dict()).connect_timeout_s == 42.0


# ---------------------------------------------------------------------------
# EngineSpec
# ---------------------------------------------------------------------------

class TestEngineSpec:
    def test_dict_round_trip(self):
        spec = EngineSpec(replica_id="r0", role="prefill",
                          model={"hidden": 16}, device_index=1,
                          fault="kill9@2", replay=True)
        back = EngineSpec.from_dict(spec.as_dict())
        assert back == spec
        # and the dict is JSON-serializable (it crosses the spawn
        # boundary as the worker entry arg)
        json.dumps(spec.as_dict())

    def test_role_validated(self):
        with pytest.raises(ValueError, match="role"):
            EngineSpec(replica_id="r0", role="decode")


# ---------------------------------------------------------------------------
# fleet digest
# ---------------------------------------------------------------------------

class TestFleetRowsDigest:
    def test_routing_invariance_and_prefill_exclusion(self):
        rows = {"req000": [1, 2, 3], "req001": [4, 5]}
        base = fleet_rows_digest(rows)
        # insertion order must not matter (rows merge from live
        # replicas and replayed journals in arbitrary order)
        assert fleet_rows_digest(
            {"req001": [4, 5], "req000": [1, 2, 3]}) == base
        # prefill probes are plumbing, not requests
        assert fleet_rows_digest(
            {**rows, "pf:req000": [9, 9]}) == base
        # but a real content change must show
        assert fleet_rows_digest(
            {"req000": [1, 2, 3], "req001": [4, 6]}) != base

    def test_digest_is_short_hex(self):
        d = fleet_rows_digest({"a": [1]})
        assert len(d) == 12
        int(d, 16)


# ---------------------------------------------------------------------------
# autoscale policy
# ---------------------------------------------------------------------------

class TestAutoscalePolicy:
    def test_scales_up_on_backlog_with_flat_slope(self):
        p = AutoscalePolicy(min_replicas=1, max_replicas=4,
                            up_backlog=4.0, cooldown=0)
        assert p.decide(0, 1, 8, None) == "up"

    def test_improving_slope_suppresses_up(self):
        p = AutoscalePolicy(min_replicas=1, max_replicas=4,
                            up_backlog=4.0, up_slope=0.0, cooldown=0)
        trends = {"queue_depth": {"slope": -2.0}}
        assert p.decide(0, 1, 8, trends) is None

    def test_max_replicas_caps_up(self):
        p = AutoscalePolicy(min_replicas=1, max_replicas=2,
                            cooldown=0)
        assert p.decide(0, 2, 100, None) is None

    def test_scales_down_after_idle_rounds(self):
        p = AutoscalePolicy(min_replicas=1, max_replicas=4,
                            down_backlog=0.5, down_rounds=3,
                            cooldown=0)
        assert p.decide(0, 2, 0, None) is None
        assert p.decide(1, 2, 0, None) is None
        assert p.decide(2, 2, 0, None) == "down"

    def test_min_replicas_floors_down(self):
        p = AutoscalePolicy(min_replicas=1, max_replicas=4,
                            down_rounds=1, cooldown=0)
        assert p.decide(0, 1, 0, None) is None

    def test_cooldown_separates_actions(self):
        p = AutoscalePolicy(min_replicas=1, max_replicas=4,
                            up_backlog=1.0, cooldown=3)
        assert p.decide(5, 1, 10, None) == "up"
        # next two rounds sit inside the cooldown window
        assert p.decide(6, 2, 10, None) is None
        assert p.decide(7, 2, 10, None) is None
        assert p.decide(8, 2, 10, None) == "up"

    def test_bounds_validated(self):
        with pytest.raises(ValueError):
            AutoscalePolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            AutoscalePolicy(min_replicas=0, max_replicas=2)


# ---------------------------------------------------------------------------
# QoS admission
# ---------------------------------------------------------------------------

class TestQoSPolicy:
    def test_class_of(self):
        assert QoSPolicy.class_of(2) == "p2"
        assert QoSPolicy.class_of(None) == "p0"

    def test_admits_under_cap_refuses_at_cap(self):
        q = QoSPolicy([QoSClass("p1", max_open=2)])
        assert q.admit("p1", 1, ()) == (True, "")
        ok, reason = q.admit("p1", 2, ())
        assert not ok and reason == "class_backlog"

    def test_uncapped_class_admits(self):
        q = QoSPolicy([QoSClass("p1", max_open=2)])
        assert q.admit("p0", 10 ** 6, ()) == (True, "")

    def test_shed_on_burn_refuses_only_matching_class(self):
        q = QoSPolicy([QoSClass("p2", shed_on_burn=True)])
        ok, reason = q.admit("p2", 0, ["p2/ttft_p99"])
        assert not ok and reason == "slo_burn"
        # a different class's burn episode must not shed p2
        assert q.admit("p2", 0, ["p0/ttft_p99"]) == (True, "")
        # a class without shed_on_burn ignores its own burns
        assert q.admit("p0", 0, ["p0/ttft_p99"]) == (True, "")

    def test_shed_policy_per_class_high_water_fallback(self):
        shed = ShedPolicy(queue_hw=8, class_queue_hw={"p2": 2})
        q = QoSPolicy([], shed=shed)
        # p2 carries its own (tighter) ceiling
        assert q.admit("p2", 1, ()) == (True, "")
        assert q.admit("p2", 2, ()) == (False, "class_backlog")
        # everyone else inherits the global mark
        assert q.admit("p0", 7, ()) == (True, "")
        assert q.admit("p0", 8, ()) == (False, "class_backlog")

    def test_duplicate_class_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            QoSPolicy([QoSClass("p0"), QoSClass("p0")])


# ---------------------------------------------------------------------------
# fault kinds (satellite: kill9 / rpc_timeout)
# ---------------------------------------------------------------------------

class TestProcessFaultKinds:
    def test_kill9_and_rpc_timeout_parse(self):
        inj = parse_fault("kill9@2,rpc_timeout@1")
        assert inj is not None and len(inj.specs) == 2

    def test_unknown_kind_fails_at_parse_time(self):
        with pytest.raises(ValueError, match="bad fault spec"):
            parse_fault("kill10@2")

    def test_malformed_step_fails_at_parse_time(self):
        with pytest.raises(ValueError, match="bad fault spec"):
            parse_fault("kill9@two")

    def test_split_fault_partitions_parent_and_child(self):
        child, parent = split_fault("kill9@2,rpc_timeout@1")
        assert child == "kill9@2"
        assert parent == "rpc_timeout@1"
        assert split_fault("rpc_timeout@3") == (None, "rpc_timeout@3")
        assert split_fault("crash@1") == ("crash@1", None)
        assert split_fault(None) == (None, None)

    def test_split_fault_validates_whole_spec(self):
        with pytest.raises(ValueError):
            split_fault("kill9@2,bogus@1")

    def test_drop_rpc_once_at_or_after(self):
        inj = parse_fault("rpc_timeout@3")
        assert not inj.drop_rpc(2)
        # the supervisor may only poll AFTER the armed round (the
        # replica could be mid-restart on round 3) — the spec must
        # defer, fire once, then stay disarmed
        assert inj.drop_rpc(5)
        assert not inj.drop_rpc(6)
        assert inj.fired() == ["rpc_timeout@3"]

    def test_kill9_is_process_fatal(self):
        assert "kill9" in PROCESS_FATAL_KINDS
        assert "rpc_timeout" in PARENT_KINDS
        assert "rpc_timeout" not in PROCESS_FATAL_KINDS


# ---------------------------------------------------------------------------
# metrics-port layout (satellite: the port-collision regression)
# ---------------------------------------------------------------------------

class TestReplicaMetricsPort:
    def test_layout_base_plus_one_plus_index(self):
        assert replica_metrics_port(9200, 0) == 9201
        assert replica_metrics_port(9200, 3) == 9204

    def test_bad_inputs_rejected(self):
        with pytest.raises(ValueError):
            replica_metrics_port(0, 0)
        with pytest.raises(ValueError):
            replica_metrics_port(9200, -1)

    def test_port_collision_error_names_the_contract(self):
        # the regression this layout replaces: two servers told to
        # bind the same port used to die with a bare EADDRINUSE
        # traceback deep in socketserver — now the error must name
        # the per-replica port contract
        first = MetricsServer(MetricsExporter(), port=0)
        port = first.start()
        try:
            second = MetricsServer(MetricsExporter(), port=port)
            with pytest.raises(OSError,
                               match="replica_metrics_port"):
                second.start()
        finally:
            first.stop()

    def test_distinct_replica_ports_coexist(self):
        first = MetricsServer(MetricsExporter(), port=0)
        base = first.start()
        second = MetricsServer(MetricsExporter(), port=0)
        try:
            assert second.start() != base
        finally:
            second.stop()
            first.stop()


# ---------------------------------------------------------------------------
# supervisor-trace pairing checks (satellite: trace_check --serve)
# ---------------------------------------------------------------------------

def _write_jsonl(path, events):
    with open(path, "w") as f:
        for e in events:
            f.write(e.to_json() + "\n")
    return str(path)


def _fleet_event(name, t=1.0, step=None, **attrs):
    return Event(time=t, step=step, kind="fleet", name=name,
                 value=attrs.pop("value", None), attrs=attrs)


def _paired_lifecycle():
    return [
        _fleet_event("replica_spawned", replica="r0", incarnation=1,
                     pid=100, role="serve", replayed=0),
        _fleet_event("replica_spawned", replica="r0", incarnation=2,
                     pid=101, role="serve", replayed=2),
        _fleet_event("replica_reaped", replica="r0", incarnation=1,
                     pid=100, reason="kill9"),
        _fleet_event("replica_reaped", replica="r0", incarnation=2,
                     pid=101, reason="shutdown"),
    ]


class TestServeTracePairing:
    def test_paired_lifecycle_passes(self, tmp_path):
        path = _write_jsonl(tmp_path / "sup.jsonl",
                            _paired_lifecycle())
        failures = check_serve_trace(path)
        assert not any("replica" in f and "reaped" in f
                       for f in failures), failures

    def test_spawn_without_reap_fails(self, tmp_path):
        events = _paired_lifecycle()[:-1]   # drop incarnation 2 reap
        path = _write_jsonl(tmp_path / "sup.jsonl", events)
        failures = check_serve_trace(path)
        assert any("incarnation 2" in f and "replica_reaped" in f
                   for f in failures), failures

    def test_reap_without_spawn_fails(self, tmp_path):
        events = _paired_lifecycle() + [
            _fleet_event("replica_reaped", replica="r9",
                         incarnation=1, pid=999, reason="drain")]
        path = _write_jsonl(tmp_path / "sup.jsonl", events)
        failures = check_serve_trace(path)
        assert any("r9" in f and "without a replica_spawned" in f
                   for f in failures), failures

    def test_autoscale_action_validated(self, tmp_path):
        events = _paired_lifecycle() + [
            _fleet_event("autoscale", step=3, action="sideways",
                         reason="backlog_trend", replica="r0",
                         backlog=9, replicas=2)]
        path = _write_jsonl(tmp_path / "sup.jsonl", events)
        failures = check_serve_trace(path)
        assert any("invalid action" in f for f in failures), failures

    def test_autoscale_replica_needs_lifecycle_events(self, tmp_path):
        events = _paired_lifecycle() + [
            _fleet_event("autoscale", step=3, action="up",
                         reason="backlog_trend", replica="r7",
                         backlog=9, replicas=2)]
        path = _write_jsonl(tmp_path / "sup.jsonl", events)
        failures = check_serve_trace(path)
        assert any("no lifecycle events" in f
                   for f in failures), failures

    def test_good_autoscale_event_passes(self, tmp_path):
        events = _paired_lifecycle() + [
            _fleet_event("autoscale", step=3, action="up",
                         reason="backlog_trend", replica="r0",
                         backlog=9, replicas=2)]
        path = _write_jsonl(tmp_path / "sup.jsonl", events)
        failures = check_serve_trace(path)
        assert not any("autoscale" in f for f in failures), failures


# ---------------------------------------------------------------------------
# monitor-summary control-plane digest (satellite: monitor_summary)
# ---------------------------------------------------------------------------

class TestSummaryControlPlane:
    def _events(self):
        return _paired_lifecycle() + [
            _fleet_event("replica_restart", step=2, replica="r0",
                         restarts=1, reason="kill9", backoff_s=0.05),
            _fleet_event("rpc_timeout", step=1, replica="r1",
                         op="snapshot", injected=True),
            _fleet_event("request_shed_admission", rid="req007",
                         priority_class="p2", reason="slo_burn"),
            _fleet_event("autoscale", step=3, action="up",
                         reason="backlog_trend", replica="r1",
                         backlog=9, replicas=2),
        ]

    def test_digest_counts(self):
        digest = summarize(self._events())
        cp = digest["serving"]["control_plane"]
        assert cp["spawned"] == 2
        assert cp["reaped"] == 2
        assert cp["replayed_requests"] == 2
        assert cp["rpc_timeouts"] == 1
        assert len(cp["restarts"]) == 1
        assert cp["restarts"][0]["replica"] == "r0"
        assert cp["shed_admission"] == {"p2/slo_burn": 1}
        assert len(cp["autoscale"]) == 1
        assert cp["autoscale"][0]["action"] == "up"

    def test_render_carries_autoscale_trace(self):
        text = render(summarize(self._events()))
        assert "control plane: 2 spawned / 2 reaped" in text
        assert "RESTART r0" in text
        assert "autoscale trace" in text
        assert "round 3: UP" in text and "r1 [backlog_trend]" in text

    def test_no_fleet_events_no_section(self):
        digest = summarize([Event(time=1.0, step=1, kind="timer",
                                  name="step", value=1.0)])
        assert "control_plane" not in digest.get("serving", {})


# ---------------------------------------------------------------------------
# end-to-end subprocess drills (slow: each fleet run spawns real
# children, ~15 s of jax import + warmup apiece on CPU)
# ---------------------------------------------------------------------------

# small-shape fleet: the 2-replica / 4-request reference trace every
# drill below must reproduce token-identically
_FLEET_KW = dict(replicas=2, max_new_tokens=3, hidden=16,
                 num_layers=1, num_heads=2, vocab=64, max_seq=64,
                 decode_attention="reference", seed=0)
_N_REQ = 4


@pytest.fixture(scope="module")
def reference_summary():
    from apex_tpu.testing.standalone_gpt import fleet_procs_smoke

    return fleet_procs_smoke(_N_REQ, **_FLEET_KW)


@pytest.mark.slow
class TestProcessFleetDrills:
    def test_uninterrupted_accounting(self, reference_summary):
        s = reference_summary
        assert s.requests_done == _N_REQ
        assert s.lost_requests == 0
        assert s.restarts == 0
        assert s.offered - s.shed_admission \
            == s.requests_done + s.rejected

    def test_kill9_replay_is_digest_identical(self, tmp_path,
                                              reference_summary):
        # the satellite-4 cross-process replay drill: incarnation 1
        # of r0 is SIGKILL'd mid-serve, its on-disk journal is
        # replayed by a FRESH process, and the merged fleet digest
        # must equal the uninterrupted run's — exactly-once across
        # the process boundary
        from apex_tpu.testing.standalone_gpt import fleet_procs_smoke

        s = fleet_procs_smoke(_N_REQ, fault="kill9@2",
                              fault_replica="r0",
                              journal_dir=str(tmp_path),
                              **_FLEET_KW)
        assert s.restarts >= 1
        assert s.replayed_requests >= 1
        assert s.lost_requests == 0
        assert s.requests_done == _N_REQ
        assert s.digest == reference_summary.digest

    def test_rpc_timeout_degrades_without_stall(self,
                                                reference_summary):
        # a dropped gauge poll marks the replica stale (router-score
        # penalty) but must never block a round or kill the replica
        from apex_tpu.testing.standalone_gpt import fleet_procs_smoke

        s = fleet_procs_smoke(_N_REQ, fault="rpc_timeout@1",
                              **_FLEET_KW)
        assert s.rpc_timeouts >= 1
        assert s.restarts == 0
        assert s.lost_requests == 0
        assert s.digest == reference_summary.digest

    def test_tick_seed_sweep_across_process_boundary(
            self, reference_summary):
        # satellite 4's schedule_sweep analogue: permuting the
        # supervisor's per-round replica tick order must not move
        # the digest
        from apex_tpu.analysis.schedule import process_sweep

        report = process_sweep([0, 1], replicas=2,
                               num_requests=_N_REQ, new_tokens=3)
        assert report.failures() == []
        assert report.runs[0].digest == reference_summary.digest
