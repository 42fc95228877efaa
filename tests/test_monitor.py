"""apex_tpu.monitor: the structured run-telemetry spine.

Deterministic CPU tests (fake clocks — no sleeps on the alarm-semantics
paths) proving:

- once-per-episode watchdog alarms: stall, non-finite loss, overflow
  streak (ISSUE 2 acceptance);
- the live heartbeat thread actually fires off the main thread;
- JsonlSink round-trip: events written by a real monitored train step
  parse back through monitor_summary, including a crash-truncated tail;
- amp scale telemetry from both StepInfo and bare ScalerState;
- Timers: the never-started-name KeyError fix, the add_scalar adapter,
  and the events() export;
- logging consolidation: exactly one handler on the apex_tpu root.
"""
import json
import logging
import threading

import pytest

from apex_tpu.monitor import (Event, JsonlSink, MemorySink, ScalarWriter,
                              StepMonitor, TeeSink, Watchdog, WriterSink,
                              load_events, render, summarize)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# Events + sinks
# ---------------------------------------------------------------------------

class TestEvent:
    def test_json_roundtrip(self):
        e = Event(time=12.5, step=3, kind="metric", name="loss",
                  value=1.25, attrs={"a": 1, "b": "x"})
        rt = Event.from_json(e.to_json())
        assert rt == e

    def test_nonfinite_value_stays_valid_json(self):
        e = Event(time=1.0, step=0, kind="metric", name="loss",
                  value=float("nan"))
        line = e.to_json()
        # strict JSON: bare NaN must not appear
        assert "NaN" not in line
        assert json.loads(line)["value"] == "nan"

    def test_device_scalar_values_coerce(self):
        import jax.numpy as jnp

        e = Event(time=1.0, step=0, kind="metric", name="x",
                  value=jnp.float32(2.5), attrs={"n": jnp.int32(3)})
        d = json.loads(e.to_json())
        assert d["value"] == 2.5 and d["attrs"]["n"] == 3.0


class TestSinks:
    def test_jsonl_append_only_and_tolerant_parse(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        with JsonlSink(path) as sink:
            for i in range(3):
                sink.emit(Event(time=float(i), step=i, kind="metric",
                                name="loss", value=float(i)))
        # simulate a kill mid-write: truncated trailing line
        with open(path, "a") as f:
            f.write('{"time": 3.0, "step": 3, "ki')
        events, malformed = load_events(path)
        assert len(events) == 3 and malformed == 1
        assert [e.value for e in events] == [0.0, 1.0, 2.0]

    def test_tee_and_writer_sink(self):
        mem = MemorySink()
        scalars = []

        class FakeTB:
            def add_scalar(self, tag, value, step):
                scalars.append((tag, value, step))

        tee = TeeSink(mem, WriterSink(FakeTB()))
        tee.emit(Event(time=0.0, step=7, kind="metric", name="loss",
                       value=2.0))
        assert len(mem.events) == 1
        assert scalars == [("metric/loss", 2.0, 7)]

    def test_scalar_writer_adapter(self):
        mem = MemorySink()
        w = ScalarWriter(mem, clock=FakeClock(5.0))
        w.add_scalar("forward-time", 0.25, 11)
        (e,) = mem.events
        assert (e.kind, e.name, e.value, e.step) == \
            ("timer", "forward-time", 0.25, 11)


# ---------------------------------------------------------------------------
# Watchdog: once-per-episode alarm semantics (fake clock, deterministic)
# ---------------------------------------------------------------------------

class TestWatchdogStall:
    def test_stall_fires_exactly_once_per_episode(self):
        mem = MemorySink()
        clk = FakeClock()
        wd = Watchdog(mem, stall_timeout=10.0, clock=clk,
                      wall_clock=clk)
        wd.observe_step(0)
        clk.advance(9.0)
        assert not wd.check_stall()
        clk.advance(2.0)          # 11 s since the last step
        assert wd.check_stall()
        # still stalled: NO second alarm this episode
        clk.advance(100.0)
        assert not wd.check_stall()
        assert len(mem.by_name("stall")) == 1
        # progress re-arms and records the recovery
        wd.observe_step(1)
        assert len(mem.by_name("stall_recovered")) == 1
        clk.advance(11.0)
        assert wd.check_stall()   # second episode
        assert len(mem.by_name("stall")) == 2

    def test_stall_attrs_carry_last_step(self):
        mem = MemorySink()
        clk = FakeClock()
        wd = Watchdog(mem, stall_timeout=5.0, clock=clk, wall_clock=clk)
        wd.observe_step(42)
        clk.advance(6.0)
        wd.check_stall()
        (alarm,) = mem.by_name("stall")
        assert alarm.attrs["last_step"] == 42
        assert alarm.value == pytest.approx(6.0)

    def test_heartbeat_thread_fires_off_main_thread(self):
        """The live path: a real (short) timeout, the daemon thread
        notices the stall while the 'main thread' does nothing — the
        situation the watchdog exists for."""
        fired = threading.Event()

        class SignalSink(MemorySink):
            def emit(self, e):
                super().emit(e)
                if e.kind == "alarm" and e.name == "stall":
                    fired.set()

        sink = SignalSink()
        wd = Watchdog(sink, stall_timeout=0.05,
                      heartbeat_interval=0.01).start()
        try:
            assert fired.wait(timeout=10.0), "heartbeat never fired"
        finally:
            wd.stop()
        assert len(sink.by_name("stall")) == 1


class TestWatchdogLossAndOverflow:
    def test_nonfinite_loss_once_per_episode(self):
        mem = MemorySink()
        wd = Watchdog(mem, clock=FakeClock(), wall_clock=FakeClock())
        wd.observe_step(0, loss=1.0)
        wd.observe_step(1, loss=float("nan"))
        wd.observe_step(2, loss=float("nan"))   # same episode
        assert len(mem.by_name("nonfinite_loss")) == 1
        wd.observe_step(3, loss=0.9)            # recovery re-arms
        wd.observe_step(4, loss=float("inf"))   # new episode
        alarms = mem.by_name("nonfinite_loss")
        assert len(alarms) == 2
        assert alarms[0].step == 1 and alarms[1].step == 4

    def test_overflow_streak_once_per_episode(self):
        mem = MemorySink()
        wd = Watchdog(mem, overflow_streak=3, clock=FakeClock(),
                      wall_clock=FakeClock())
        for i in range(2):
            wd.observe_step(i, overflow=True)
        assert not mem.by_name("overflow_streak")   # below threshold
        wd.observe_step(2, overflow=True)           # streak hits 3
        wd.observe_step(3, overflow=True)           # same episode
        (alarm,) = mem.by_name("overflow_streak")
        assert alarm.step == 2 and alarm.value == 3
        wd.observe_step(4, overflow=False)          # finite step re-arms
        for i in range(5, 8):
            wd.observe_step(i, overflow=True)
        assert len(mem.by_name("overflow_streak")) == 2

    def test_occasional_overflow_never_alarms(self):
        mem = MemorySink()
        wd = Watchdog(mem, overflow_streak=3, clock=FakeClock(),
                      wall_clock=FakeClock())
        for i in range(20):   # healthy dynamic-scaler pattern
            wd.observe_step(i, overflow=(i % 2 == 0))
        assert not mem.by_name("overflow_streak")


# ---------------------------------------------------------------------------
# StepMonitor: derived metrics + amp scale telemetry
# ---------------------------------------------------------------------------

class TestStepMonitor:
    def test_derived_metrics(self):
        mem = MemorySink()
        clk = FakeClock()
        mon = StepMonitor(mem, tokens_per_step=1000,
                          flops_per_step=5e9, peak_flops=1e12,
                          clock=clk, wall_clock=clk)
        mon.start_step(0)
        clk.advance(0.1)
        mon.end_step(0, loss=2.0, grad_norm=1.5, lr=3e-4)
        mon.close()
        m = {e.name: e.value for e in mem.by_kind("metric")}
        assert m["loss"] == 2.0 and m["grad_norm"] == 1.5
        assert m["lr"] == pytest.approx(3e-4)
        assert m["step_ms"] == pytest.approx(100.0)
        assert m["tokens_per_sec"] == pytest.approx(10000.0)
        assert m["mfu"] == pytest.approx(5e9 / 0.1 / 1e12)
        names = [e.name for e in mem.by_kind("run")]
        assert names == ["run_start", "run_end"]

    def test_nonfinite_loss_metric_is_flagged_and_alarmed(self):
        mem = MemorySink()
        mon = StepMonitor(mem, watchdog=Watchdog(
            mem, clock=FakeClock(), wall_clock=FakeClock(),
            heartbeat_interval=60.0))
        mon.start_step(0)
        mon.end_step(0, loss=float("nan"))
        mon.close()
        (loss_e,) = mem.by_name("loss")
        assert loss_e.value is None and loss_e.attrs["nonfinite"] == "nan"
        assert len(mem.by_name("nonfinite_loss")) == 1

    def test_scale_events_from_step_info(self):
        from apex_tpu.amp import StepInfo

        mem = MemorySink()
        mon = StepMonitor(mem, watchdog=Watchdog(
            mem, overflow_streak=2, clock=FakeClock(),
            wall_clock=FakeClock(), heartbeat_interval=60.0))
        infos = [
            StepInfo(False, 32768.0, 1),   # overflow: backoff
            StepInfo(False, 16384.0, 2),   # overflow again -> streak 2
            StepInfo(True, 16384.0, 2),    # healthy
        ]
        for i, info in enumerate(infos):
            mon.start_step(i)
            mon.end_step(i, loss=1.0, scaler=info)
        mon.close()
        scales = [e.value for e in mem.by_name("loss_scale")]
        assert scales == [32768.0, 16384.0, 16384.0]
        overflows = mem.by_name("overflow")
        assert [e.step for e in overflows] == [0, 1]
        (alarm,) = mem.by_name("overflow_streak")
        assert alarm.step == 1 and alarm.value == 2

    def test_scale_events_from_bare_scaler_state(self):
        """Without the measured finite flag (grads not inspected), the
        skip is inferred from the steps_skipped counter delta."""
        from apex_tpu.amp import scaler as sc

        mem = MemorySink()
        mon = StepMonitor(mem)
        import jax.numpy as jnp

        s0 = sc.init("dynamic")
        s1 = sc.update(s0, jnp.bool_(False))   # overflow
        s2 = sc.update(s1, jnp.bool_(True))    # fine
        for i, s in enumerate((s0, s1, s2)):
            mon.start_step(i)
            mon.end_step(i, scaler=s)
        mon.close()
        overflows = mem.by_name("overflow")
        assert [e.step for e in overflows] == [1]
        scales = [e.value for e in mem.by_name("loss_scale")]
        assert scales[1] == pytest.approx(scales[0] / 2)

    def test_update_telemetry_contract(self):
        from apex_tpu.amp import StepInfo
        from apex_tpu.amp import scaler as sc

        t = sc.update_telemetry(None, StepInfo(False, 2.0, 1))
        assert t["overflow"] and t["checked"] and t["loss_scale"] == 2.0
        prev = {"loss_scale": 2.0, "steps_skipped": 1}
        t = sc.update_telemetry(prev, StepInfo(True, 4.0, 1))
        assert not t["overflow"] and t["scale_changed"]
        # unchecked StepInfo (static scaler): fall back to the delta
        t = sc.update_telemetry(prev,
                                StepInfo(True, 2.0, 2,
                                         grads_checked=False))
        assert t["overflow"] and not t["checked"]


# ---------------------------------------------------------------------------
# Timers: KeyError fix + adapter + events() export
# ---------------------------------------------------------------------------

class TestTimers:
    def _timers(self):
        from apex_tpu.transformer.pipeline_parallel.utils import Timers

        t = Timers()
        t("fwd").start()
        t("fwd").stop()
        return t

    def test_write_and_log_skip_never_started_names(self, capsys):
        t = self._timers()
        written = []

        class W:
            def add_scalar(self, *a):
                written.append(a)

        # 'bwd' was never started: must be skipped, not a KeyError
        t.write(["fwd", "bwd"], W(), iteration=3)
        assert len(written) == 1 and written[0][0] == "fwd-time"
        t.log(["fwd", "bwd"])
        out = capsys.readouterr().out
        assert "fwd" in out and "bwd" not in out

    def test_write_through_scalar_adapter_lands_in_sink(self):
        t = self._timers()
        mem = MemorySink()
        t.write(["fwd"], ScalarWriter(mem), iteration=5)
        (e,) = mem.events
        assert e.kind == "timer" and e.name == "fwd-time" and e.step == 5

    def test_events_export(self):
        t = self._timers()
        t("bwd").start()
        t("bwd").stop()
        mem = MemorySink()
        t.events(mem, iteration=2)
        names = sorted(e.name for e in mem.events)
        assert names == ["bwd", "fwd"]
        assert all(e.kind == "timer" and e.step == 2 for e in mem.events)
        # missing names skipped here too
        t.events(mem, iteration=3, names=["nope"])
        assert len(mem.events) == 2


# ---------------------------------------------------------------------------
# End-to-end: monitored train smoke -> JSONL -> summary (the acceptance
# path tools/ci.sh runs as a process; here in-process and asserted)
# ---------------------------------------------------------------------------

class TestMonitoredSmokeRoundTrip:
    def test_gpt_smoke_writes_parseable_run_log(self, tmp_path, capsys):
        from apex_tpu.monitor import summary as summod
        from apex_tpu.testing.standalone_gpt import train_smoke

        path = str(tmp_path / "gpt_run.jsonl")
        loss = train_smoke(steps=2, jsonl=path)
        assert loss == loss   # finite

        events, malformed = load_events(path)
        assert malformed == 0
        kinds = {e.kind for e in events}
        assert {"run", "metric", "scale", "timer"} <= kinds
        metric_names = {e.name for e in events if e.kind == "metric"}
        # the acceptance list: loss, tokens/s, step ms (+ the rest)
        assert {"loss", "tokens_per_sec", "step_ms", "grad_norm",
                "lr", "mfu"} <= metric_names
        assert any(e.kind == "scale" and e.name == "loss_scale"
                   for e in events)
        assert any(e.kind == "timer" for e in events)

        s = summarize(events)
        assert s["steps"]["count"] == 2
        assert s["scale"]["last"] > 0
        out = render(s)
        assert "amp scale" in out and "phase" in out

        # the CLI contract CI keys off
        assert summod.main([path]) == 0
        assert "steps: 2" in capsys.readouterr().out

    @pytest.mark.slow
    def test_bert_smoke_same_event_stream(self, tmp_path):
        from apex_tpu.testing.standalone_bert import train_smoke

        mem = MemorySink()
        train_smoke(steps=2, sink=mem)
        kinds = {e.kind for e in mem.events}
        assert {"run", "metric", "scale", "timer"} <= kinds
        run = mem.by_name("run_start")[0]
        assert run.attrs["driver"] == "standalone_bert.train_smoke"


# ---------------------------------------------------------------------------
# Logging consolidation (the duplicate-handler satellite)
# ---------------------------------------------------------------------------

class TestLoggingConsolidation:
    def test_single_handler_no_propagate(self):
        import apex_tpu  # noqa: F401  (import installs the handler)
        from apex_tpu.utils.log_util import get_logger

        get_logger(__name__)   # a second configure call must not stack
        root = logging.getLogger("apex_tpu")
        assert len(root.handlers) == 1
        assert root.propagate is False

    def test_get_logger_accepts_dotted_and_path_names(self):
        from apex_tpu.utils.log_util import get_logger

        assert get_logger("apex_tpu.ops.flash_attention").name == \
            "apex_tpu.ops.flash_attention"
        assert get_logger("ops.thing").name == "apex_tpu.ops.thing"
        assert get_logger("/a/b/my_module.py").name == \
            "apex_tpu.my_module"

    def test_fallback_log_routes_through_library_logger(self):
        """propagate=False keeps library records off the root logger
        (user logging config untouched), so capture on the apex_tpu
        logger itself."""
        from apex_tpu.ops import flash_attention as fa

        records = []

        class Capture(logging.Handler):
            def emit(self, record):
                records.append(record)

        root = logging.getLogger("apex_tpu")
        handler = Capture(level=logging.INFO)
        old_level = root.level
        root.addHandler(handler)
        root.setLevel(logging.INFO)
        try:
            fa._E_FALLBACK_SEEN.clear()
            fa._log_e_fallback("test reason", 1, 2, 3, 4)
        finally:
            root.removeHandler(handler)
            root.setLevel(old_level)
            fa._E_FALLBACK_SEEN.clear()
        assert any("test reason" in r.getMessage() for r in records)
        assert records[0].name == "apex_tpu.ops.flash_attention"

    def test_top_level_formatter_reexport(self):
        import apex_tpu
        from apex_tpu.utils.log_util import RankInfoFormatter

        assert apex_tpu.RankInfoFormatter is RankInfoFormatter
