"""Per-request serving telemetry tests (ISSUE-11):
request-lifecycle event chains (every submitted rid ends in exactly
one terminal event; queued+prefill+decode sums to the request wall),
TTFT/queue-wait/ITL distributions in ServeSummary, engine tick-gauge
cadence at K=1 and K=4, SIGTERM-drain chain completeness, the
exactly-once engine snapshot trigger, the per-request Chrome lanes
round-tripped through ``check_serve_trace``, and the serve loop's
watchdog stall heartbeat.
"""
import json
import os
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.monitor import (Event, JsonlSink, MemorySink,
                              StepMonitor, Watchdog, load_events,
                              summarize, render)
from apex_tpu.monitor.tracing import (check_serve_trace,
                                      chrome_trace_from_events,
                                      write_chrome_trace)
from apex_tpu.serving import (BucketLadder, EngineGauges, Request,
                              RequestTrace, ServeMetrics,
                              ServingEngine, ServingModelConfig,
                              SnapshotTrigger, default_cache_config,
                              extract_serving_weights)
from apex_tpu.testing.standalone_gpt import GPTModel, serve_smoke


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class FakeClock:
    """Deterministic monotonic clock: every read advances 1s."""

    def __init__(self, t=0.0, dt=1.0):
        self.t = t
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


class StubMonitor:
    """Minimal StepMonitor facade: event() into a MemorySink, plus an
    optional watchdog attribute — no heartbeat thread, so fake clocks
    stay single-threaded."""

    def __init__(self, sink=None, watchdog=None):
        self.sink = sink if sink is not None else MemorySink()
        self.watchdog = watchdog

    def event(self, kind, name, value=None, step=None, **attrs):
        self.sink.emit(Event(time=float(step or 0), step=step,
                             kind=kind, name=name, value=value,
                             attrs=attrs))


class FlagAutoResume:
    """AutoResume stand-in: terminate when the flag is set."""

    source = "test"

    def __init__(self):
        self.flag = False

    def termination_requested(self):
        return self.flag


def _tiny_model(vocab=32, hidden=16, heads=2, layers=2, max_seq=32,
                seed=0):
    model = GPTModel(
        vocab_size=vocab, hidden_size=hidden, num_layers=layers,
        num_attention_heads=heads, max_sequence_length=max_seq,
        attention_dropout=0.0, hidden_dropout=0.0, use_flash=False,
        dtype=jnp.float32)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(model, params, *, ladder, num_blocks=16, block_size=4,
            monitor=None, autoresume=None, tick_every=None,
            snapshot=None):
    cfg = ServingModelConfig.from_model(
        model, prefill_flash=False, decode_attention="reference")
    weights = extract_serving_weights(params, cfg.num_layers)
    cache_cfg = default_cache_config(cfg, num_blocks=num_blocks,
                                     block_size=block_size)
    return ServingEngine(weights, cfg, cache_cfg, ladder=ladder,
                         monitor=monitor, autoresume=autoresume,
                         tick_every=tick_every, snapshot=snapshot)


def _serve(monitor, *, n=3, new=3, ladder=None, tick_every=None,
           autoresume=None, snapshot=None):
    model, params = _tiny_model()
    eng = _engine(model, params,
                  ladder=ladder or BucketLadder(batch=(2, 4),
                                                pages=(3,)),
                  monitor=monitor, autoresume=autoresume,
                  tick_every=tick_every, snapshot=snapshot)
    for i in range(n):
        eng.submit(Request(rid=f"r{i}", prompt=[3 + i, 7, (5 * i) % 32],
                           max_new_tokens=new))
    summary = eng.run()
    return eng, summary


# ---------------------------------------------------------------------------
# RequestTrace / ServeMetrics units (fake clock)
# ---------------------------------------------------------------------------

class TestRequestTrace:
    def test_parts_sum_to_wall_exactly(self):
        # phase boundaries are shared instants, so the identity is
        # exact — the checker's 2% tolerance only covers ms rounding
        tr = RequestTrace(rid="r", prompt_len=3, submit_t=10.0,
                          submit_tick=0, admit_t=13.5, admit_tick=1,
                          first_token_t=14.25, done_t=20.0,
                          done_tick=5, new_tokens=4)
        assert tr.queue_wait_s + tr.prefill_s + tr.decode_s \
            == pytest.approx(tr.wall_s, abs=1e-12)
        assert tr.queue_wait_s == pytest.approx(3.5)
        assert tr.prefill_s == pytest.approx(0.75)
        assert tr.ttft_s == pytest.approx(4.25)
        assert tr.decode_tokens_per_sec == pytest.approx(3 / 5.75)

    def test_never_admitted_is_all_queue_wait(self):
        tr = RequestTrace(rid="r", prompt_len=3, submit_t=1.0,
                          submit_tick=0, done_t=9.0, done_tick=2,
                          preempted=True)
        assert not tr.admitted
        assert tr.ttft_s is None
        assert tr.queue_wait_s == pytest.approx(tr.wall_s) == 8.0
        assert tr.prefill_s == tr.decode_s == 0.0
        row = tr.lane_row()
        assert row["prefill_ms"] is None and row["decode_ms"] is None


class TestServeMetricsUnit:
    def _req(self, rid="r0", prompt=(1, 2, 3), new=3):
        return Request(rid=rid, prompt=list(prompt),
                       max_new_tokens=new)

    def test_lifecycle_events_and_distributions(self):
        clock = FakeClock()                      # init consumes t=1
        mon = StubMonitor()
        m = ServeMetrics(monitor=mon, clock=clock, tick_every=1)
        req = self._req()
        m.on_submit(req, 0)                      # submit_t = 2
        m.on_admit(req, 0, admit_t=clock(),      # admit_t = 3
                   prefill_s=2.0)                # first token @ 5
        req.out_tokens = [5, 6, 7]
        req.token_latency_s = [2.0, 0.5, 0.25]
        req.preempted = False
        clock.t = 10.0
        m.on_done(req, 2)                        # done_t = 11
        names = [e.name for e in mon.sink.by_kind("serving")]
        assert names == ["request_submitted", "request_admitted",
                         "request_first_token", "request_done"]
        done = mon.sink.by_name("request_done")[0].attrs
        assert done["queue_wait_ms"] == pytest.approx(1000.0)
        assert done["prefill_ms"] == pytest.approx(2000.0)
        assert done["ttft_ms"] == pytest.approx(3000.0)
        assert done["decode_ms"] == pytest.approx(6000.0)
        assert done["queue_wait_ms"] + done["prefill_ms"] \
            + done["decode_ms"] == pytest.approx(done["wall_ms"])
        pct = m.percentiles()
        assert pct["ttft_p50_ms"] == pytest.approx(3000.0)
        assert pct["queue_wait_p99_ms"] == pytest.approx(1000.0)
        # ITL = decode-tick latencies (the prefill sample excluded)
        assert pct["itl_p50_ms"] == pytest.approx(375.0)
        dists = m.distributions()
        assert dists["itl_ms"]["n"] == 2
        assert "decode_tokens_per_sec" in dists

    def test_rejection_counts(self):
        mon = StubMonitor()
        m = ServeMetrics(monitor=mon, clock=FakeClock(), tick_every=1)
        m.on_reject("a", "ladder_span", 0)
        m.on_reject("b", "ladder_span", 0)
        m.on_reject("c", "max_seq", 1)
        assert m.rejected == {"ladder_span": 2, "max_seq": 1}
        evs = mon.sink.by_name("request_rejected")
        assert len(evs) == 3
        assert evs[0].attrs["reason"] == "ladder_span"


class TestEngineGauges:
    def test_cadence_k4_with_trailing_flush(self):
        g = EngineGauges(every=4)
        emitted = []
        for t in range(1, 11):          # 10 ticks
            if t in (2, 7):
                g.on_admit()
            if t == 9:
                g.on_finish(preempted=False)
            out = g.observe(t, batch=2, used_blocks=t,
                            queue_depth=0, compiles=3)
            if out is not None:
                emitted.append(out)
        tail = g.flush()
        assert tail is not None
        emitted.append(tail)
        assert g.flush() is None        # nothing pending twice
        assert len(emitted) == 3        # ceil(10/4)
        assert [e["ticks"] for e in emitted] == [4, 4, 2]
        assert [e["admitted"] for e in emitted] == [1, 1, 0]
        assert sum(e["finished"] for e in emitted) == 1
        # high water is monotone across windows
        assert [e["used_blocks_high_water"] for e in emitted] \
            == [4, 8, 10]
        # compile deltas: all 3 charged to the first window
        assert [e["new_compiles"] for e in emitted] == [3, 0, 0]

    def test_cadence_k1_emits_every_tick(self):
        g = EngineGauges(every=1)
        outs = [g.observe(t, batch=1, used_blocks=1) for t in range(5)]
        assert all(o is not None for o in outs)
        assert g.flush() is None


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

class TestLifecycleThroughEngine:
    def test_every_rid_ends_in_exactly_one_terminal(self):
        mon = StubMonitor()
        eng, summary = _serve(mon, n=3, new=3)
        srv = mon.sink.by_kind("serving")
        for rid in ("r0", "r1", "r2"):
            chain = [e.name for e in srv
                     if e.attrs.get("rid") == rid]
            assert chain == ["request_submitted", "request_admitted",
                             "request_first_token", "request_done"]
        done = mon.sink.by_name("request_done")
        assert len(done) == 3
        for e in done:
            a = e.attrs
            assert not a["preempted"] and "ttft_ms" in a
            parts = a["queue_wait_ms"] + a["prefill_ms"] \
                + a["decode_ms"]
            # the acceptance bar: parts sum to the rid's wall <= 2%
            assert parts == pytest.approx(a["wall_ms"],
                                          rel=0.02, abs=1e-3)
        assert summary.ttft_p50_ms is not None
        assert summary.ttft_p99_ms >= summary.ttft_p50_ms
        assert summary.queue_wait_p50_ms is not None
        assert summary.itl_p50_ms is not None
        assert summary.requests_rejected == {}

    def test_rejected_submit_counts_reasons(self):
        mon = StubMonitor()
        model, params = _tiny_model()
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(2,), pages=(2,)),
                      monitor=mon)
        with pytest.raises(ValueError, match="span"):
            eng.submit(Request(rid="big", prompt=list(range(7)),
                               max_new_tokens=8))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(Request(rid="neg", prompt=[1],
                               max_new_tokens=0))
        eng.submit(Request(rid="ok", prompt=[1, 2],
                           max_new_tokens=2))
        s = eng.run()
        assert s.requests_rejected == {"ladder_span": 1,
                                       "max_new_tokens": 1}
        assert len(mon.sink.by_name("request_rejected")) == 2
        # rejected rids never get lifecycle chains
        assert not [e for e in mon.sink.by_kind("serving")
                    if e.attrs.get("rid") == "big"
                    and e.name != "request_rejected"]

    def test_tick_gauges_k1(self):
        mon = StubMonitor()
        eng, _ = _serve(mon, n=2, new=4, tick_every=1)
        gauges = mon.sink.by_kind("serve_tick")
        # one per decode tick, plus the run-end flush carrying the
        # final tick's evictions (the tick that evicts decodes
        # nothing, so only the flush can report it)
        assert len(gauges) == eng.steps + 1
        a = gauges[0].attrs
        for key in ("batch", "batch_bucket", "pages_bucket",
                    "free_blocks", "used_blocks", "reserved_blocks",
                    "pool_blocks", "queue_depth", "ticks", "admitted",
                    "finished", "preempted", "new_compiles",
                    "used_blocks_high_water"):
            assert key in a, key
        assert all(g.attrs["ticks"] == 1 for g in gauges[:-1])
        assert gauges[-1].attrs["ticks"] == 0
        assert sum(g.attrs["admitted"] for g in gauges) == 2
        assert sum(g.attrs["finished"] for g in gauges) == 2
        assert sum(g.attrs["ticks"] for g in gauges) == eng.steps

    def test_tick_gauges_k4_cadence_and_flush(self):
        mon = StubMonitor()
        eng, _ = _serve(mon, n=2, new=6, tick_every=4)
        gauges = mon.sink.by_kind("serve_tick")
        assert eng.steps == 5          # 1 prefill + 5 decode tokens
        # a full K=4 window at tick 4, then one flush covering the
        # trailing tick AND the final evictions
        assert [g.attrs["ticks"] for g in gauges] == [4, 1]
        assert sum(g.attrs["ticks"] for g in gauges) == eng.steps
        assert sum(g.attrs["admitted"] for g in gauges) == 2
        assert sum(g.attrs["finished"] for g in gauges) == 2

    def test_sigterm_drain_chains_complete(self, tmp_path):
        # ladder caps the batch at 1, so 2 of 3 requests are still
        # queued when termination lands mid-decode: the in-flight one
        # AND the never-admitted ones all end in terminal events
        jsonl = tmp_path / "drain.jsonl"
        sink = JsonlSink(str(jsonl))
        mon = StubMonitor(sink=MemorySink())
        mon.sink = sink  # engine emits through the file sink

        class Tee:
            def __init__(self, s):
                self.events = []
                self.s = s

            def emit(self, e):
                self.events.append(e)
                self.s.emit(e)
        tee = Tee(sink)
        mon.sink = tee
        ar = FlagAutoResume()
        model, params = _tiny_model()
        eng = _engine(model, params,
                      ladder=BucketLadder(batch=(1,), pages=(3,)),
                      monitor=mon, autoresume=ar)
        for i in range(3):
            eng.submit(Request(rid=f"r{i}", prompt=[2, 4 + i],
                               max_new_tokens=8))
        eng.run(after_tick=lambda i: setattr(ar, "flag", i >= 1))
        sink.close()
        done = [e for e in tee.events if e.name == "request_done"]
        assert len(done) == 3
        preempted = [e for e in done if e.attrs["preempted"]]
        assert len(preempted) == 3
        never_admitted = [e for e in preempted
                          if "ttft_ms" not in e.attrs]
        assert len(never_admitted) == 2
        for e in never_admitted:
            # the whole wall was queue wait
            assert e.attrs["queue_wait_ms"] == pytest.approx(
                e.attrs["wall_ms"], rel=0.02, abs=1e-3)
        # the drained log passes the serve checker (preempted chains
        # are complete without first-token events)
        assert check_serve_trace(str(jsonl)) == []

    def test_watchdog_heartbeat_per_tick(self):
        clock = FakeClock()
        sink = MemorySink()
        wd = Watchdog(sink, stall_timeout=1000.0, clock=clock,
                      wall_clock=lambda: 0.0)
        mon = StubMonitor(sink=sink, watchdog=wd)
        eng, _ = _serve(mon, n=2, new=3)
        assert eng.steps > 0
        # observe_step ran at every tick: progress is recent, so a
        # stall check just under the timeout stays quiet...
        assert not wd.check_stall(now=clock.t + 999.0)
        # ...and one past it fires exactly once (per episode)
        assert wd.check_stall(now=clock.t + 1001.0)
        assert not wd.check_stall(now=clock.t + 1002.0)
        alarm = sink.by_name("stall")[0]
        assert alarm.attrs["last_step"] == eng.steps


# ---------------------------------------------------------------------------
# snapshot trigger
# ---------------------------------------------------------------------------

class TestSnapshotTrigger:
    def test_file_trigger_exactly_once(self, tmp_path):
        f = tmp_path / "snap"
        f.touch()
        mon = StubMonitor()
        trig = SnapshotTrigger(trigger_file=str(f))
        state = {"tick": 3, "active": 2,
                 "requests": [{"rid": "a", "seq_len": 4}]}
        assert trig.poll(3, lambda: state, mon)
        assert not f.exists()                  # consumed
        assert not trig.poll(4, lambda: state, mon)   # no re-fire
        evs = mon.sink.by_name("engine_snapshot")
        assert len(evs) == 1
        assert evs[0].attrs["reason"] == "file"
        assert evs[0].attrs["active"] == 2
        # nested state survives the JSONL round trip as real JSON
        parsed = json.loads(evs[0].to_json())
        assert parsed["attrs"]["requests"][0]["rid"] == "a"
        # a second touch arms a second (exactly one) snapshot
        f.touch()
        assert trig.poll(5, lambda: state, mon)
        assert len(mon.sink.by_name("engine_snapshot")) == 2

    def test_signal_trigger_flag_only(self):
        mon = StubMonitor()
        trig = SnapshotTrigger(signum=signal.SIGUSR1)
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            # handler only set the flag; the event lands at poll
            assert mon.sink.by_name("engine_snapshot") == []
            assert trig.poll(1, lambda: {"tick": 1}, mon)
            assert not trig.poll(2, lambda: {"tick": 2}, mon)
            evs = mon.sink.by_name("engine_snapshot")
            assert len(evs) == 1 and evs[0].attrs["reason"] == "signal"
        finally:
            trig.close()

    def test_unconsumable_trigger_file_fires_once(self, tmp_path,
                                                  monkeypatch):
        # a file that cannot be unlinked (read-only trigger dir) must
        # not re-arm every tick: one snapshot, then the file source
        # retires
        f = tmp_path / "snap"
        f.touch()
        mon = StubMonitor()
        trig = SnapshotTrigger(trigger_file=str(f))

        def deny(_):
            raise OSError("read-only")
        monkeypatch.setattr("apex_tpu.serving.metrics.os.unlink",
                            deny)
        assert trig.poll(1, lambda: {"tick": 1}, mon)
        assert trig.trigger_file is None
        assert not trig.poll(2, lambda: {"tick": 2}, mon)
        assert len(mon.sink.by_name("engine_snapshot")) == 1

    def test_state_failure_never_kills_the_poll(self):
        mon = StubMonitor()
        trig = SnapshotTrigger()
        trig.request("manual")

        def boom():
            raise RuntimeError("wedged")
        assert trig.poll(1, boom, mon)
        e = mon.sink.by_name("engine_snapshot")[0]
        assert "wedged" in e.attrs["error"]

    def test_engine_snapshot_state_through_run(self, tmp_path):
        f = tmp_path / "snap"
        f.touch()
        mon = StubMonitor()
        trig = SnapshotTrigger(trigger_file=str(f))
        eng, _ = _serve(mon, n=2, new=3, snapshot=trig)
        evs = mon.sink.by_name("engine_snapshot")
        assert len(evs) == 1
        a = evs[0].attrs
        assert a["tick"] == 1 and a["active"] == 2
        assert a["pool_blocks"] == eng.cache_cfg.usable_blocks
        assert len(a["requests"]) == 2


# ---------------------------------------------------------------------------
# Chrome lanes + check_serve_trace round trip
# ---------------------------------------------------------------------------

class TestChromeLanes:
    def _run_to_jsonl(self, tmp_path, **kw):
        jsonl = tmp_path / "serve.jsonl"
        sink = JsonlSink(str(jsonl))
        mon = StubMonitor()
        mon.sink = sink
        eng, summary = _serve(mon, **kw)
        sink.close()
        return jsonl, eng, summary

    def test_roundtrip_through_checker(self, tmp_path):
        jsonl, eng, _ = self._run_to_jsonl(tmp_path, n=3, new=3)
        chrome = tmp_path / "serve.chrome.json"
        write_chrome_trace(str(chrome), eng.metrics.chrome_trace())
        assert check_serve_trace(str(jsonl), str(chrome)) == []
        trace = json.loads(chrome.read_text())
        lanes = [t for t in trace["traceEvents"]
                 if t.get("cat") == "serve"]
        rids = {t["args"]["rid"] for t in lanes}
        assert rids == {"r0", "r1", "r2"}
        assert {t["name"] for t in lanes} \
            == {"queued", "prefill", "decode"}
        # each rid's lane is contiguous: phases abut in time.  Stamps
        # are epoch microseconds (~1.8e15, where a float64 resolves
        # 0.25 us), so compare offsets from the trace's first stamp
        # and hold them to a few of those steps, not to 0.01 us
        t0 = min(t["ts"] for t in lanes)
        for rid in rids:
            mine = sorted((t for t in lanes
                           if t["args"]["rid"] == rid),
                          key=lambda t: t["ts"])
            for a, b in zip(mine, mine[1:]):
                assert (a["ts"] - t0) + a["dur"] == pytest.approx(
                    b["ts"] - t0, abs=1.0)

    def test_lanes_rebuilt_from_event_log(self, tmp_path):
        # the read-side join: monitor_summary --chrome on any serve
        # JSONL reconstructs the same lanes from terminal events
        jsonl, _, _ = self._run_to_jsonl(tmp_path, n=2, new=3)
        events, malformed = load_events(str(jsonl))
        assert malformed == 0
        trace = chrome_trace_from_events(events)
        lanes = [t for t in trace["traceEvents"]
                 if t.get("cat") == "serve"]
        assert {t["args"]["rid"] for t in lanes} == {"r0", "r1"}
        chrome = tmp_path / "rebuilt.chrome.json"
        write_chrome_trace(str(chrome), trace)
        assert check_serve_trace(str(jsonl), str(chrome)) == []

    def test_checker_failure_modes(self, tmp_path):
        jsonl, eng, _ = self._run_to_jsonl(tmp_path, n=2, new=3)
        lines = jsonl.read_text().splitlines()
        # drop one terminal event: a submitted rid with no terminal
        torn = tmp_path / "torn.jsonl"
        torn.write_text("\n".join(
            ln for ln in lines
            if '"request_done"' not in ln
            or '"rid":"r1"' not in ln) + "\n")
        fails = check_serve_trace(str(torn))
        assert any("r1" in f and "terminal" in f for f in fails)
        # strip ttft off a finished request: TTFT must exist for
        # every non-preempted rid
        doctored = []
        for ln in lines:
            if '"request_done"' in ln and '"rid":"r0"' in ln:
                d = json.loads(ln)
                d["attrs"].pop("ttft_ms")
                ln = json.dumps(d, separators=(",", ":"))
            doctored.append(ln)
        bad = tmp_path / "bad.jsonl"
        bad.write_text("\n".join(doctored) + "\n")
        fails = check_serve_trace(str(bad))
        assert any("r0" in f and "ttft" in f for f in fails)
        # a chrome artifact missing a lane fails
        chrome = tmp_path / "empty.chrome.json"
        write_chrome_trace(str(chrome),
                           {"traceEvents": [], "displayTimeUnit": "ms"})
        fails = check_serve_trace(str(jsonl), str(chrome))
        assert any("no lane" in f for f in fails)


# ---------------------------------------------------------------------------
# summary + driver integration
# ---------------------------------------------------------------------------

class TestServeSummaryAndDriver:
    def test_summary_serving_section(self, tmp_path):
        jsonl = tmp_path / "serve.jsonl"
        serve_smoke(3, max_new_tokens=3, jsonl=str(jsonl),
                    ladder=BucketLadder(batch=(2, 4), pages=(2,)),
                    num_blocks=24, block_size=4, autoresume=None,
                    snapshot=None)
        events, _ = load_events(str(jsonl))
        digest = summarize(events)
        srv = digest["serving"]
        assert srv["submitted"] == 3 and srv["done"] == 3
        assert srv["preempted"] == 0
        lat = srv["latency"]
        for series in ("queue_wait_ms", "ttft_ms", "itl_ms"):
            assert lat[series]["p50"] <= lat[series]["p99"]
        assert srv["pool_high_water_blocks"] >= 1
        assert sum(srv["bucket_ticks"].values()) > 0
        text = render(digest)
        assert "serving: 3 submitted" in text
        assert "ttft" in text and "pool high water" in text

    def test_summary_itl_population_matches_summary_fields(self):
        # the digest's ITL series weights each decode tick by its
        # batch (every active request gains one token per tick), so
        # monitor_summary's p99 agrees with ServeSummary.itl_p99_ms
        mon = StubMonitor()
        eng, summary = _serve(mon, n=3, new=4)
        digest = summarize(list(mon.sink.events))
        d = digest["serving"]["latency"]["itl_ms"]
        n_samples = sum(e.attrs["batch"] for e in mon.sink.events
                        if e.name == "decode_step")
        assert d["n"] == n_samples
        # summary fields round to 3 decimals; the math is identical
        assert d["p99"] == pytest.approx(summary.itl_p99_ms,
                                         abs=1e-3)

    def test_serve_smoke_trace_dir_writes_lanes(self, tmp_path):
        jsonl = tmp_path / "serve.jsonl"
        tr = tmp_path / "tr"
        summary = serve_smoke(
            2, max_new_tokens=3, jsonl=str(jsonl),
            ladder=BucketLadder(batch=(2,), pages=(2,)),
            num_blocks=24, block_size=4, autoresume=None,
            snapshot=None, trace_dir=str(tr))
        chrome = tr / "serve.chrome.json"
        assert chrome.exists()
        assert check_serve_trace(str(jsonl), str(chrome)) == []
        assert summary.ttft_p50_ms is not None
        assert summary.ttft_p50_ms > 0
        # warmed admission: TTFT measures serving, not AOT compiles —
        # the whole serve took far less than one compile
        assert summary.queue_wait_p99_ms < 60_000

    def test_serve_summary_dict_round_trips_json(self):
        mon = StubMonitor()
        _, summary = _serve(mon, n=2, new=3)
        d = summary.as_dict()
        for k in ("queue_wait_p50_ms", "ttft_p99_ms", "itl_p50_ms",
                  "requests_rejected"):
            assert k in d
        json.dumps(d)   # the bench row / serve_done event shape
        done_ev = mon.sink.by_name("serve_done")[0]
        assert "ttft_p99_ms" in done_ev.attrs


# ---------------------------------------------------------------------------
# chunked prefill spanning ticks (ISSUE-12)
# ---------------------------------------------------------------------------

class TestMultiTickPrefillLifecycle:
    def _req(self, rid="r0", prompt=(1, 2, 3), new=3):
        return Request(rid=rid, prompt=list(prompt),
                       max_new_tokens=new)

    def test_split_admit_first_token_chain(self):
        # chunked prefill: request_admitted at prefill start,
        # request_first_token TICKS later at the real first token —
        # TTFT and the parts-sum identity measured to that instant
        clock = FakeClock()                      # init consumes t=1
        mon = StubMonitor()
        m = ServeMetrics(monitor=mon, clock=clock, tick_every=1)
        req = self._req()
        m.on_submit(req, 0)                      # submit_t = 2
        m.on_admit(req, 0, admit_t=clock(),      # admit_t = 3
                   prefill_s=None, warm_tokens=0)
        clock.t = 7.0
        m.on_first_token(req, 2, clock())        # first token @ 8
        req.out_tokens = [5, 6, 7]
        req.token_latency_s = [5.0, 0.5, 0.25]
        clock.t = 10.0
        m.on_done(req, 4)                        # done_t = 11
        names = [e.name for e in mon.sink.by_kind("serving")]
        assert names == ["request_submitted", "request_admitted",
                         "request_first_token", "request_done"]
        admitted = mon.sink.by_name("request_admitted")[0]
        assert admitted.value is None            # duration unknown yet
        ft = mon.sink.by_name("request_first_token")[0]
        assert ft.attrs["ttft_ms"] == pytest.approx(6000.0)
        assert ft.attrs["prefill_ms"] == pytest.approx(5000.0)
        done = mon.sink.by_name("request_done")[0].attrs
        assert done["prefill_ms"] == pytest.approx(5000.0)
        assert done["queue_wait_ms"] + done["prefill_ms"] \
            + done["decode_ms"] == pytest.approx(done["wall_ms"])
        assert m.percentiles()["ttft_p50_ms"] == pytest.approx(6000.0)

    def test_preempted_mid_prefill_parts_still_sum(self):
        # a request drained while its chunked prefill was running has
        # no first token: its post-admission wall reads as prefill,
        # the chain stays complete, and no ttft_ms is claimed
        clock = FakeClock()
        mon = StubMonitor()
        m = ServeMetrics(monitor=mon, clock=clock, tick_every=1)
        req = self._req()
        m.on_submit(req, 0)                      # submit_t = 2
        m.on_admit(req, 0, admit_t=clock(), prefill_s=None)  # t = 3
        req.preempted = True
        clock.t = 8.0
        m.on_done(req, 3)                        # done_t = 9
        done = mon.sink.by_name("request_done")[0].attrs
        assert done["preempted"] and "ttft_ms" not in done
        assert done["prefill_ms"] == pytest.approx(6000.0)
        assert done["decode_ms"] == 0.0
        assert done["queue_wait_ms"] + done["prefill_ms"] \
            == pytest.approx(done["wall_ms"])
        assert not mon.sink.by_name("request_first_token")

    def test_chunked_serve_passes_trace_check(self, tmp_path):
        # the acceptance bar end to end: lifecycle chains complete
        # (N submitted => N terminal, TTFT on every finished rid,
        # parts-sum <= 2%) when every prefill spans multiple ticks
        jsonl = tmp_path / "serve.jsonl"
        summary = serve_smoke(
            4, max_new_tokens=3, jsonl=str(jsonl),
            ladder=BucketLadder(batch=(2, 4), pages=(4,),
                                chunks=(2,)),
            num_blocks=48, block_size=4, autoresume=None,
            snapshot=None, prefill_chunk=2)
        assert summary.requests_done == 4
        assert summary.prefill_chunks >= 4
        assert check_serve_trace(str(jsonl)) == []
        assert summary.ttft_p50_ms is not None

    def test_spec_serve_passes_trace_check(self, tmp_path):
        jsonl = tmp_path / "serve.jsonl"
        summary = serve_smoke(
            3, max_new_tokens=4, jsonl=str(jsonl),
            ladder=BucketLadder(batch=(2, 4), pages=(2,)),
            num_blocks=24, block_size=4, autoresume=None,
            snapshot=None, speculate_k=2, draft="self")
        assert summary.spec_accept_rate == 1.0
        assert check_serve_trace(str(jsonl)) == []


class TestResilienceMetrics:
    """ISSUE-13: terminal reasons on the lifecycle chain, shed/deadline
    gauge counters, and the crash-replay chain reopen semantics."""

    def test_terminal_reason_rides_request_done(self):
        mon = StubMonitor()
        m = ServeMetrics(monitor=mon, clock=FakeClock(), tick_every=1)
        req = Request(rid="d", prompt=[1, 2], max_new_tokens=4)
        req.terminal = "deadline_exceeded"
        m.on_submit(req, 0)
        m.on_done(req, 1)
        done = mon.sink.by_name("request_done")[0].attrs
        assert done["terminal"] == "deadline_exceeded"
        assert done["preempted"] is False
        # never admitted: the whole wall is queue wait, parts sum
        assert done["queue_wait_ms"] == pytest.approx(done["wall_ms"])

    def test_gauges_count_shed_and_deadline_windows(self):
        g = EngineGauges(every=2)
        g.on_finish("shed")
        g.on_finish("shed")
        g.on_finish("deadline")
        g.on_finish("finished")
        g.observe(1, batch=1, used_blocks=1, compiles=0)
        out = g.observe(2, batch=1, used_blocks=1, compiles=0)
        assert out["shed"] == 2
        assert out["deadline_exceeded"] == 1
        assert out["finished"] == 1
        # counters reset per window; a clean window omits the keys
        out2 = g.flush()
        assert out2 is None or "shed" not in out2

    def test_flush_carries_tickless_shed_window(self):
        g = EngineGauges(every=4)
        g.on_finish("shed")
        tail = g.flush()
        assert tail is not None and tail["shed"] == 1

    def test_reopen_resets_incarnation_parts_sum(self):
        # a crash-replayed rid: queue wait spans the crash downtime to
        # the FRESH admission; prefill/decode measure the incarnation
        # that finishes — parts still sum to the rid's full wall
        clock = FakeClock()
        m = ServeMetrics(monitor=StubMonitor(), clock=clock,
                         tick_every=1)
        req = Request(rid="r", prompt=[1, 2, 3], max_new_tokens=3)
        m.on_submit(req, 0)                      # submit_t = 2
        m.on_admit(req, 0, admit_t=clock(), prefill_s=1.0)
        tr = m.reopen("r")
        assert tr is not None
        assert tr.admit_t is None and tr.first_token_t is None
        assert tr.submit_t == 2.0                # original anchor
        m.on_admit(req, 3, admit_t=clock(), prefill_s=0.5)
        req.out_tokens = [7, 8]
        req.token_latency_s = [0.5, 0.25]
        m.on_done(req, 4)
        done = m.completed[-1]
        assert done.queue_wait_s + done.prefill_s + done.decode_s \
            == pytest.approx(done.wall_s, abs=1e-9)
        assert m.reopen("ghost") is None
