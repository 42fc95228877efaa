"""``readers/handoff_idle_ms`` on hand traces: a program run linked to
the dispatch that launched it and to the fetch that waits for it, the
idle time on each edge, the window, and None where there is nothing to
read.  The six metrics of the serving engine's hand-off and of the
collector are held to their files' ``example`` by the contract test in
``test_benchmark_harness.py``; here the reader itself, and that every
span an example names is one the program opens."""
import inspect

import pytest

from apex_tpu.monitor import tracing
from apex_tpu.serving import engine
from benchmarks import run as bench_run
from benchmarks.readers import handoff_idle_ms, span_idle_ms
from benchmarks.trace import DeviceTrace, Event, make_trace

MS = 1e-3
STEP = r"^apex\.serve\.step$"
DISPATCH = r"^apex\.serve\.(decode|prefill)\.dispatch$"
FETCH = r"^apex\.serve\.(decode|prefill)\.fetch$"
OP = "%fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop"


def trace(runs, program_spans, window=(0, 40), module="jit_step(1)"):
    """One device whose ``runs`` (``start, dur`` in ms) are each a
    program run with one op over all of it, one ``bench.engine_step``
    span over ``window``, and the program's spans."""
    device = DeviceTrace([Event(module, a * MS, d * MS) for a, d in runs],
                         [Event(OP, a * MS, d * MS) for a, d in runs])
    return make_trace(
        [device],
        [Event("bench.engine_step", window[0] * MS,
               (window[1] - window[0]) * MS)],
        [Event(n, a * MS, d * MS) for n, a, d in program_spans])


def read(t, edge, **params):
    params = {"edge": edge,
              "span_pattern": DISPATCH if edge == "launch" else FETCH,
              "per_pattern": STEP, **params}
    return handoff_idle_ms.read(t, {}, params, {})


# one tick: dispatch 1.5-2.5, the program 3.7-9.7, fetch 2.5-10.5
TICK = [("apex.serve.step", 0, 11), ("apex.serve.decode.build", 0.5, 1),
        ("apex.serve.decode.dispatch", 1.5, 1),
        ("apex.serve.decode.fetch", 2.5, 8)]


def test_launch_is_the_idle_time_from_the_dispatch_to_its_program():
    t = trace([(3.7, 6)], TICK)
    assert read(t, "launch") == pytest.approx(2.2)


def test_wake_is_the_idle_time_from_the_programs_end_to_the_fetchs():
    t = trace([(3.7, 6)], TICK)
    assert read(t, "wake") == pytest.approx(0.8)


def test_a_launch_latency_inside_the_fetch_is_launch_and_not_wake():
    # 1.2 ms of the 2.2 lie inside the fetch, before the program starts
    t = trace([(3.7, 6)], TICK)
    both = read(t, "launch") + read(t, "wake")
    step_idle = span_idle_ms.read(t, {}, {"span_pattern": STEP}, {})
    # the rest of the step's idle time is the host's own: 0-1.5 build
    # and schedule, 10.5-11 after the fetch
    assert both == pytest.approx(3.0)
    assert step_idle == pytest.approx(both + 1.5 + 0.5)


def test_a_dispatch_while_the_device_is_busy_reads_zero():
    # a prefill chunk runs 1.0-4.0; the tick dispatched at 1.5 starts
    # behind it at 4.0: nothing of its launch is exposed
    spans = [("apex.serve.step", 0, 11),
             ("apex.serve.prefill.dispatch", 0.6, 0.3),
             ("apex.serve.decode.dispatch", 1.5, 1),
             ("apex.serve.decode.fetch", 2.5, 8)]
    t = trace([(1.0, 3.0), (4.0, 5.0)], spans)
    assert read(t, "launch") == pytest.approx(0.4)      # the chunk's own
    only_decode = r"^apex\.serve\.decode\.dispatch$"
    assert read(t, "launch", span_pattern=only_decode) \
        == pytest.approx(0.0, abs=1e-12)
    # the wake of the tick: 9.0-10.5
    assert read(t, "wake") == pytest.approx(1.5)


def test_a_dispatch_with_no_program_before_the_next_counts_nothing():
    # the first dispatch launched nothing of the pattern's (a copy, a
    # program of another name); its program is the second dispatch's
    spans = [("apex.serve.step", 0, 20),
             ("apex.serve.decode.dispatch", 1, 1),
             ("apex.serve.decode.dispatch", 5, 1),
             ("apex.serve.decode.fetch", 6, 10)]
    t = trace([(7, 8)], spans)
    assert read(t, "launch") == pytest.approx(2.0)      # 5-7 alone
    # a program of another name is no program of the pattern's
    other = trace([(7, 8)], spans, module="jit_cow(3)")
    assert read(other, "launch") == 0.0
    assert read(other, "launch", module_pattern=r"^jit_cow\(") \
        == pytest.approx(2.0)


def test_a_run_the_trace_puts_just_before_its_dispatch_is_still_its_own():
    # the device's events on the host's clock sit 0.3 ms early: each
    # tick's program is its own dispatch's (launch 0), not the next
    # tick's, which would count the whole gap between ticks
    spans = [("apex.serve.step", 0, 11),
             ("apex.serve.decode.dispatch", 1.5, 1),
             ("apex.serve.decode.fetch", 2.5, 8),
             ("apex.serve.step", 12, 11),
             ("apex.serve.decode.dispatch", 13.5, 1),
             ("apex.serve.decode.fetch", 14.5, 8)]
    t = trace([(1.2, 6), (13.2, 6)], spans)
    assert read(t, "launch") == 0.0
    assert read(t, "wake") == pytest.approx(3.3)
    step_idle = span_idle_ms.read(t, {}, {"span_pattern": STEP}, {})
    assert step_idle == pytest.approx(5.0)
    # a run that began longer ago than the slack is earlier work
    t = trace([(2.5, 3)], [("apex.serve.step", 0, 10),
                           ("apex.serve.decode.dispatch", 5, 1),
                           ("apex.serve.decode.fetch", 6, 3)])
    assert read(t, "launch") == 0.0
    assert handoff_idle_ms.CLOCK_SLACK_S == pytest.approx(1e-3)


def test_a_fetch_takes_the_last_program_that_started_before_its_end():
    # two programs, the prefill's and its draft's: the fetch waits for
    # the later one, and what it waits before that one ends is busy
    spans = [("apex.serve.step", 0, 12),
             ("apex.serve.prefill.dispatch", 0.5, 1),
             ("apex.serve.prefill.fetch", 1.5, 10)]
    t = trace([(2, 3), (6, 4)], spans)
    assert read(t, "wake") == pytest.approx(1.5)        # 10-11.5
    assert read(t, "launch") == pytest.approx(1.5)      # 0.5-2
    # a fetch that returns before its program ends waited no wake
    t = trace([(2, 12)], spans, window=(0, 20))
    assert read(t, "wake") == 0.0
    # a fetch with no program before it counts nothing
    t = trace([(30, 2)], spans)
    assert read(t, "wake") == 0.0


def test_spans_divide_by_the_steps_and_are_held_to_the_window():
    spans = [("apex.serve.step", -12, 11),
             ("apex.serve.decode.dispatch", -10.5, 1),  # before: not counted
             ("apex.serve.decode.fetch", -9.5, 8),
             ("apex.serve.step", 0, 11),
             ("apex.serve.decode.dispatch", 1.5, 1),
             ("apex.serve.decode.fetch", 2.5, 8),
             ("apex.serve.step", 38, 11),
             ("apex.serve.decode.dispatch", 38.5, 1),   # cut at the end
             ("apex.serve.decode.fetch", 39.5, 8)]
    t = trace([(-8, 6), (3.7, 6), (39.8, 6)], spans)
    assert read(t, "launch") == pytest.approx((2.2 + 1.3) / 2)
    assert read(t, "wake") == pytest.approx(0.8 / 2)


def test_none_where_there_is_nothing_to_read():
    assert handoff_idle_ms.read(None, {}, {"edge": "launch",
                                           "span_pattern": DISPATCH}, {}) \
        is None
    # no device ops (the CPU's trace)
    empty = make_trace([DeviceTrace([], [])],
                       [Event("bench.engine_step", 0, 40 * MS)],
                       [Event(n, a * MS, d * MS) for n, a, d in TICK])
    assert read(empty, "launch") is None
    # ops but no span of the pattern: the parent's prefill, say
    t = trace([(3.7, 6)], TICK)
    assert read(t, "launch",
                span_pattern=r"^apex\.serve\.prefill\.dispatch$") is None
    # the spans are there, the steps to divide by are not
    assert read(trace([(3.7, 6)], TICK[1:]), "wake") is None


@pytest.mark.parametrize("name, reader", [
    ("launch_idle_ms.chat", "handoff_idle_ms"),
    ("launch_idle_ms.sat", "handoff_idle_ms"),
    ("wake_idle_ms.chat", "handoff_idle_ms"),
    ("wake_idle_ms.sat", "handoff_idle_ms"),
    ("gc_idle_ms.chat", "span_idle_ms"),
    ("gc_idle_ms.sat", "span_idle_ms")])
def test_metric_files_name_their_reader_and_the_programs_spans(name,
                                                                 reader):
    spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                               name + ".json")
    assert spec["reader"] == f"benchmarks.readers.{reader}:read"
    assert spec["source"] == "program_span"
    assert spec["layer"] == "serving engine"
    assert spec["params"]["per_pattern"] == STEP
    # every span of the example is one the engine or the tracing
    # module opens
    source = inspect.getsource(engine) + inspect.getsource(tracing)
    for span_name, _, _ in spec["example"]["program_spans"]:
        assert f'span("{span_name}"' in source, span_name
