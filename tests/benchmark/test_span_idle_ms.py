"""``readers/span_idle_ms`` on hand traces: the overlap arithmetic,
the division by ``per_pattern``'s spans, the window, and None where
there is nothing to read.  The seven metrics that PR 27 adds are held
to their files' ``example`` by the contract test in
``test_benchmark_harness.py``; here the reader itself."""
import inspect

import pytest

from apex_tpu.serving import engine
from benchmarks import run as bench_run
from benchmarks.readers import idle, span_idle_ms
from benchmarks.trace import DeviceTrace, Event, make_trace

MS = 1e-3
STEP = r"^apex\.serve\.step$"
OP = "%fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop"


def trace(ops, program_spans, window=(0, 40), devices=None):
    """One device with ``ops``, one ``bench.engine_step`` span over
    ``window`` and the program's spans, all ``(name,) start, dur`` in
    milliseconds."""
    if devices is None:
        devices = [DeviceTrace([], [Event(OP, a * MS, d * MS)
                                    for a, d in ops])]
    return make_trace(
        devices,
        [Event("bench.engine_step", window[0] * MS,
               (window[1] - window[0]) * MS)],
        [Event(n, a * MS, d * MS) for n, a, d in program_spans])


def read(t, span_pattern, per_pattern=None):
    params = {"span_pattern": span_pattern}
    if per_pattern:
        params["per_pattern"] = per_pattern
    return span_idle_ms.read(t, {}, params, {})


# one tick: the device runs 2-9 ms, the host is in step() 0-10 ms
TICK = [("apex.serve.step", 0, 10), ("apex.serve.schedule", 0, 0.5),
        ("apex.serve.decode.build", 0.5, 1.0),
        ("apex.serve.decode.dispatch", 1.5, 1.0),
        ("apex.serve.decode.fetch", 2.5, 6.75),
        ("apex.serve.deliver", 9.25, 0.25),
        ("apex.serve.tick_tail", 9.5, 0.5)]


@pytest.mark.parametrize("pattern, idle_ms", [
    (STEP, 3.0),                                   # 0-2 and 9-10
    (r"^apex\.serve\.schedule$", 0.5),             # all of it
    (r"^apex\.serve\.decode\.(build|dispatch)$", 1.5),   # 0.5-2.0
    (r"^apex\.serve\.decode\.fetch$", 0.25),       # the wake-up, 9-9.25
    (r"^apex\.serve\.(decode\.fetch|deliver|tick_tail)$", 1.0),
])
def test_idle_time_inside_the_matching_spans(pattern, idle_ms):
    t = trace([(2, 7)], TICK)
    assert read(t, pattern, STEP) == pytest.approx(idle_ms)


def test_the_phases_of_a_tick_sum_to_the_whole_and_to_the_idle_reader():
    t = trace([(2, 3), (5.5, 3.5)], TICK, window=(0, 10))
    phases = sum(read(t, "^" + name.replace(".", r"\.") + "$", STEP)
                 for name, _, _ in TICK[1:])
    whole = read(t, STEP)
    assert phases == pytest.approx(whole) == pytest.approx(3.5)
    # the identity PERF.md checks on the chip: idle ms a step x steps
    # is the idle share x the window
    share = idle.read(t, {}, {}, {})
    assert whole * 1 == pytest.approx(share / 100 * t.window_s * 1e3)


def test_per_pattern_divides_by_its_own_spans():
    spans = [("apex.serve.step", 0, 10), ("apex.serve.step", 10, 10),
             ("apex.serve.admit", 1, 2)]          # one admission, two steps
    t = trace([(3, 6), (11, 8)], spans)
    assert read(t, r"^apex\.serve\.admit$") == pytest.approx(2.0)
    assert read(t, r"^apex\.serve\.admit$", STEP) == pytest.approx(1.0)
    assert read(t, STEP) == pytest.approx((4 + 2) / 2)


def test_many_short_ops_and_gaps_inside_one_span():
    # 100 ops of 0.05 ms every 0.1 ms from 1 ms on: half of 1-11 is idle
    t = trace([(1 + 0.1 * i, 0.05) for i in range(100)],
              [("apex.serve.step", 0, 12)])
    assert read(t, STEP) == pytest.approx(1 + 5 + 1)
    # a span that starts and ends inside single ops
    t = trace([(0, 4), (5, 4)], [("apex.serve.step", 2, 5)])
    assert read(t, STEP) == pytest.approx(1.0)


def test_spans_are_held_to_the_window():
    spans = [("apex.serve.step", -5, 4),     # before the window: not counted
             ("apex.serve.step", 2, 4),
             ("apex.serve.step", 38, 6)]     # cut at the window's end
    t = trace([(3, 2)], spans)
    assert read(t, STEP) == pytest.approx((2 + 2) / 2)


def test_none_where_there_is_nothing_to_read():
    assert span_idle_ms.read(None, {}, {"span_pattern": STEP}, {}) is None
    # no device ops (the CPU's trace, or the parent's on another plane)
    t = trace([], TICK)
    assert read(t, STEP) is None
    no_devices = trace([], TICK, devices=[])
    assert read(no_devices, STEP) is None
    # ops but no span of the program's: the parent commit
    assert read(trace([(2, 7)], []), STEP) is None
    # the phase is there, the span to divide by is not
    t = trace([(2, 7)], TICK[1:])
    assert read(t, r"^apex\.serve\.schedule$", STEP) is None
    assert read(t, r"^apex\.serve\.schedule$") == pytest.approx(0.5)


def test_the_first_device_that_ran_ops_is_read():
    idle_device = DeviceTrace([], [])
    busy = DeviceTrace([], [Event(OP, 2 * MS, 7 * MS)])
    t = trace(None, TICK, devices=[idle_device, busy])
    assert read(t, STEP) == pytest.approx(3.0)


@pytest.mark.parametrize("name", [
    "engine_step_host_ms.chat", "engine_step_host_ms.sat",
    "host_schedule_ms.chat", "host_build_ms.chat", "host_tail_ms.chat"])
def test_metric_files_name_this_reader_and_the_programs_spans(name):
    spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                               name + ".json")
    assert spec["reader"] == "benchmarks.readers.span_idle_ms:read"
    assert spec["source"] == "program_span"
    assert spec["params"].get("per_pattern", STEP) == STEP
    # every span of the example is one the engine opens
    source = inspect.getsource(engine)
    for span_name, _, _ in spec["example"]["program_spans"]:
        assert f'span("{span_name}")' in source, span_name
