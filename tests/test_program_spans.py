"""The program's own host spans (PR 27): ``monitor.tracing.span`` is a
``jax.profiler.TraceAnnotation`` first and a ``SpanTracer`` record
besides, and the serving engine opens one span per host phase of a
tick, on the device trace's clock.

A tiny engine runs on the CPU under ``jax.profiler``; the spans are
read back from the ``.xplane.pb`` the way the benchmark reads them
(``benchmarks.trace.load_trace``) and, for the step's counters, from
the events' stats.
"""
import glob

import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from apex_tpu.monitor import MemorySink
from apex_tpu.monitor.tracing import (SpanTracer, recording, set_tracer,
                                      span)
from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                              ServingModelConfig, default_cache_config,
                              extract_serving_weights)
from apex_tpu.testing.standalone_gpt import GPTModel
from benchmarks.trace import load_trace

PHASES = ["apex.serve.schedule", "apex.serve.admit", "apex.serve.prefill",
          "apex.serve.decode.build", "apex.serve.decode.dispatch",
          "apex.serve.decode.fetch", "apex.serve.deliver",
          "apex.serve.tick_tail"]
COUNTERS = {"batch", "batch_bucket", "pages_bucket", "admitted",
            "queue_depth", "used_blocks", "pool_blocks"}
PROMPTS = [[3, 7, 1], [11, 2, 9, 4, 5], [5, 6]]


@pytest.fixture(scope="module")
def tiny():
    model = GPTModel(vocab_size=32, hidden_size=16, num_layers=2,
                     num_attention_heads=2, max_sequence_length=32,
                     attention_dropout=0.0, hidden_dropout=0.0,
                     use_flash=False, dtype=jnp.float32)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(tiny, **kw):
    model, params = tiny
    cfg = ServingModelConfig.from_model(model, prefill_flash=False,
                                        decode_attention="reference")
    weights = extract_serving_weights(params, cfg.num_layers)
    cache_cfg = default_cache_config(cfg, num_blocks=16, block_size=4)
    return ServingEngine(weights, cfg, cache_cfg,
                         ladder=BucketLadder(batch=(2,), pages=(3,)), **kw)


def drive(engine):
    """Two requests at once, a third two ticks later; every ``step()``'s
    return value."""
    for i, p in enumerate(PROMPTS[:2]):
        engine.submit(Request(rid=f"r{i}", prompt=p, max_new_tokens=4))
    gained = [engine.step(), engine.step()]
    engine.submit(Request(rid="r2", prompt=PROMPTS[2], max_new_tokens=3))
    while engine.queue or engine.active or engine.prefilling:
        gained.append(engine.step())
    return gained


def outcome(engine, gained):
    return (gained, {q.rid: list(q.out_tokens) for q in engine.done},
            engine.tokens_digest())


def host_events(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of the program's spans."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("apex.")]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def profiled(tiny, tmp_path_factory):
    """One profiled run of the whole-prompt engine: (its outcome, the
    spans with their stats, the benchmark's view of the trace)."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    engine = make_engine(tiny)
    engine.warmup()
    jax.profiler.start_trace(trace_dir)
    try:
        gained = drive(engine)
    finally:
        jax.profiler.stop_trace()
    return (outcome(engine, gained), host_events(trace_dir),
            load_trace(trace_dir))


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_a_profiled_run_yields_every_span_of_the_table(profiled):
    _, events, trace = profiled
    names = {e[0] for e in events}
    assert names == set(PHASES) | {"apex.serve.step"}
    # the benchmark keeps them beside its own, on the trace's clock
    assert {s.name for s in trace.program_spans} == names
    assert len(trace.program_spans) == len(events)


def test_each_phase_lies_inside_a_step_and_prefill_inside_admit(profiled):
    _, events, _ = profiled
    steps = [e for e in events if e[0] == "apex.serve.step"]
    assert len(steps) == len(profiled[0][0])
    for e in events:
        if e[0] != "apex.serve.step":
            assert sum(inside(e, s) for s in steps) == 1, e[0]
    admits = [e for e in events if e[0] == "apex.serve.admit"]
    prefills = [e for e in events if e[0] == "apex.serve.prefill"]
    # one admit span a step whose queue is not empty: two that admit
    # and one that finds the batch full
    assert len(prefills) == len(PROMPTS) and len(admits) == 3
    for p in prefills:
        assert sum(inside(p, a) for a in admits) == 1
    assert sorted(sum(inside(p, a) for p in prefills)
                  for a in admits) == [0, 1, 2]
    # the phases of one step are siblings: in order, none overlapping
    for s in steps:
        mine = [e for e in events if e is not s and inside(e, s)
                and e[0] != "apex.serve.prefill"]
        assert [e[0] for e in mine] == [n for n in PHASES
                                        if n in {e[0] for e in mine}]
        assert all(a[2] <= b[1] for a, b in zip(mine, mine[1:]))


def test_the_step_span_carries_the_ticks_counters(profiled):
    (gained, _, _), events, _ = profiled
    steps = [e for e in events if e[0] == "apex.serve.step"]
    ticks = [(s, n) for s, n in zip(steps, gained) if n > 0]
    assert ticks
    for s, n in ticks:
        assert set(s[3]) == COUNTERS
        assert s[3]["batch"] == n and s[3]["batch_bucket"] == 2
        assert s[3]["pages_bucket"] == 3 and s[3]["pool_blocks"] == 15
        assert 0 < s[3]["used_blocks"] <= 15
    # r2 waits one tick for a row of the full batch
    assert [s[3]["admitted"] for s, _ in ticks] == [2, 0, 0, 1, 0]
    assert [s[3]["queue_depth"] for s, _ in ticks] == [0, 0, 1, 0, 0]
    # a step that ran no tick has no counters to carry
    assert all(not s[3] for s, n in zip(steps, gained) if n == 0)


def test_tokens_are_the_same_with_tracing_off_on_and_recorded(tiny,
                                                              profiled):
    assert not recording()
    engine = make_engine(tiny)
    plain = outcome(engine, drive(engine))
    assert plain == profiled[0]
    model, params = tiny
    for i, prompt in enumerate(PROMPTS):      # and they are the right ones
        toks = list(prompt)
        for _ in plain[1][f"r{i}"]:
            logits = model.apply({"params": params},
                                 jnp.asarray([toks], jnp.int32))
            toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
        assert toks[len(prompt):] == plain[1][f"r{i}"]
    tracer = SpanTracer()
    set_tracer(tracer)
    try:
        assert recording()
        engine = make_engine(tiny)
        assert outcome(engine, drive(engine)) == plain
    finally:
        set_tracer(None)
    spans = tracer.drain()
    assert {s.name for s in spans} == set(PHASES) | {"apex.serve.step"}
    tick = next(s for s in spans if s.name == "apex.serve.step")
    assert set(tick.attrs) == COUNTERS and tick.attrs["admitted"] == 2
    # nesting as the tracer sees it: step 0, its phases 1, prefill 2
    depth = {s.name: s.depth for s in spans}
    assert depth["apex.serve.step"] == 0
    assert depth["apex.serve.admit"] == 1
    assert depth["apex.serve.prefill"] == 2


def recorded_run(tiny, **kw):
    """The spans a ``SpanTracer`` holds after one drive, and the run's
    outcome."""
    tracer = SpanTracer()
    set_tracer(tracer)
    try:
        engine = make_engine(tiny, **kw)
        result = outcome(engine, drive(engine))
    finally:
        set_tracer(None)
    return tracer.drain(), result


def test_chunked_prefill_is_a_sibling_of_its_own(tiny):
    spans, _ = recorded_run(tiny, prefill_chunk=4)
    depth = {(s.name, s.depth) for s in spans}
    # the chunk runs straight under the step, not under an admission
    assert ("apex.serve.prefill", 1) in depth
    assert ("apex.serve.prefill", 2) not in depth
    assert ("apex.serve.admit", 1) in depth


def test_a_speculative_tick_uses_the_same_names(tiny, profiled):
    model, params = tiny
    cfg = ServingModelConfig.from_model(model, prefill_flash=False,
                                        decode_attention="reference")
    draft = extract_serving_weights(params, cfg.num_layers)
    spans, result = recorded_run(tiny, speculate_k=2, draft_weights=draft,
                                 draft_cfg=cfg)
    assert {s.name for s in spans} == set(PHASES) | {"apex.serve.step"}
    # greedy speculation emits what greedy decode emits
    assert result[1] == profiled[0][1]
    # K draft steps and one verify a tick: three dispatches, three
    # fetches, all straight under the step
    tick = next(s for s in spans if s.name == "apex.serve.step")
    first = [s for s in spans if tick.t0 <= s.t0 < tick.t0 + tick.dur]
    names = [s.name for s in first]
    assert names.count("apex.serve.decode.dispatch") >= 3
    assert names.count("apex.serve.decode.fetch") == 3
    assert all(s.depth == 1 for s in first
               if s.name.startswith("apex.serve.decode."))


def test_span_records_the_annotation_and_the_tracers_span(tmp_path):
    tracer = SpanTracer()
    set_tracer(tracer)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("apex.test.phase", tag="x") as s:
            s.set(rows=3)

        @span("apex.test.decorated")
        def work():
            return 7

        assert work() == 7 and work() == 7
    finally:
        jax.profiler.stop_trace()
        set_tracer(None)
    recorded = tracer.drain()
    assert [s.name for s in recorded] == [
        "apex.test.phase", "apex.test.decorated", "apex.test.decorated"]
    assert recorded[0].attrs == {"tag": "x", "rows": 3}
    sink = MemorySink()
    tracer2 = SpanTracer()
    set_tracer(tracer2)
    try:
        with span("apex.test.event", step=5):
            pass
    finally:
        set_tracer(None)
    tracer2.events(sink)
    (event,) = sink.events
    assert (event.kind, event.name, event.step) == (
        "span", "apex.test.event", 5)
    # the same occurrences are in the profiler's file, stats and all
    events = host_events(str(tmp_path))
    assert [e[0] for e in events] == [s.name for s in recorded]
    assert events[0][3] == {"tag": "x", "rows": 3}


def test_span_without_tracer_is_the_bare_annotation(tmp_path):
    assert not recording()
    with span("apex.test.off") as s:      # inert: no profiler either
        s.set(rows=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert recording()
        with span("apex.test.on", tag="y") as s:
            s.set(rows=2)
    finally:
        jax.profiler.stop_trace()
    assert not recording()
    (event,) = host_events(str(tmp_path))
    assert event[0] == "apex.test.on"
    assert event[3] == {"tag": "y", "rows": 2}
