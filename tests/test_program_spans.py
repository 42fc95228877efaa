"""The program's own host spans: ``monitor.tracing.span`` is a
``jax.profiler.TraceAnnotation`` first and a ``SpanTracer`` record
besides, the serving engine opens one span per host phase of a tick,
and a prefill's hand-off to the device is split as a tick's is, all on
the device trace's clock; ``apex.host.gc`` marks Python's collections
while something records.

A tiny engine runs on the CPU under ``jax.profiler``; the spans are
read back from the ``.xplane.pb`` the way the benchmark reads them
(``benchmarks.trace.load_trace``) and, for the step's counters, from
the events' stats.  A collection may fall anywhere in a run, so the
name sets below are of the ``apex.serve.`` spans.
"""
import gc
import glob

import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from apex_tpu.monitor import MemorySink, tracing
from apex_tpu.monitor.tracing import (SpanTracer, recording, set_tracer,
                                      span)
from apex_tpu.serving import (BucketLadder, Request, ServingEngine,
                              ServingModelConfig, default_cache_config,
                              extract_serving_weights)
from apex_tpu.testing.standalone_gpt import GPTModel
from benchmarks import builders_evabyte
from benchmarks.trace import load_trace

SERVE = "apex.serve."
PREFILL_PARTS = ["apex.serve.prefill.dispatch", "apex.serve.prefill.fetch"]
PHASES = ["apex.serve.schedule", "apex.serve.admit", "apex.serve.prefill",
          *PREFILL_PARTS, "apex.serve.decode.build",
          "apex.serve.decode.dispatch", "apex.serve.decode.fetch",
          "apex.serve.deliver",
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


def host_events(trace_dir, prefix=SERVE):
    """``(name, start_ns, end_ns, stats)`` of the program's spans whose
    name starts with ``prefix``."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith(prefix)]
    return sorted(out, key=lambda e: e[1])


def serve_spans(spans):
    """The engine's own of a ``SpanTracer``'s spans."""
    return [s for s in spans if s.name.startswith(SERVE)]


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
    kept = [s for s in trace.program_spans if s.name.startswith(SERVE)]
    assert {s.name for s in kept} == names
    assert len(kept) == len(events)


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
    # each prefill's hand-off: its dispatch, then its fetch, inside it
    for part in PREFILL_PARTS:
        mine = [e for e in events if e[0] == part]
        assert len(mine) == len(PROMPTS)
        assert all(sum(inside(e, p) for p in prefills) == 1 for e in mine)
    for p in prefills:
        parts = [e for e in events if e[0] in PREFILL_PARTS
                 and inside(e, p)]
        assert [e[0] for e in parts] == PREFILL_PARTS
        assert parts[0][2] <= parts[1][1]
    # the phases of one step are siblings: in order, none overlapping
    for s in steps:
        mine = [e for e in events if e is not s and inside(e, s)
                and not e[0].startswith("apex.serve.prefill")]
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
    spans = serve_spans(tracer.drain())
    assert {s.name for s in spans} == set(PHASES) | {"apex.serve.step"}
    tick = next(s for s in spans if s.name == "apex.serve.step")
    assert set(tick.attrs) == COUNTERS and tick.attrs["admitted"] == 2
    # nesting as the tracer sees it: step 0, its phases 1, prefill 2,
    # the prefill's dispatch and fetch 3
    depth = {(s.name, s.depth) for s in spans}
    assert ("apex.serve.step", 0) in depth
    assert ("apex.serve.admit", 1) in depth
    assert ("apex.serve.prefill", 2) in depth
    assert {d for n, d in depth if n in PREFILL_PARTS} == {3}


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


def chunk_parts(spans):
    """For each ``apex.serve.prefill`` of ``spans`` (a ``SpanTracer``'s,
    by start), the names of the spans inside it."""
    prefills = [s for s in spans if s.name == "apex.serve.prefill"]
    return [[s.name for s in spans if s is not p and p.t0 <= s.t0
             and s.t0 + s.dur <= p.t0 + p.dur] for p in prefills]


def test_chunked_prefill_is_a_sibling_of_its_own(tiny):
    spans, result = recorded_run(tiny, prefill_chunk=4)
    spans = serve_spans(spans)
    depth = {(s.name, s.depth) for s in spans}
    # the chunk runs straight under the step, not under an admission
    assert ("apex.serve.prefill", 1) in depth
    assert ("apex.serve.prefill", 2) not in depth
    assert ("apex.serve.admit", 1) in depth
    assert {d for n, d in depth if n in PREFILL_PARTS} == {2}
    # a chunk dispatches; only the one that completes a prompt fetches
    # (prompts of 3, 5 and 2 tokens in chunks of 4: r1 takes two)
    parts = chunk_parts(spans)
    assert sorted(map(tuple, parts)) == sorted(
        [tuple(PREFILL_PARTS)] * 3 + [("apex.serve.prefill.dispatch",)])
    # and the tokens are those of the whole-prompt path
    assert result[1] == recorded_run(tiny)[1][1]


def pooled_engine():
    """A tiny EVA model over the pooled cache (window 32, pages of 4),
    whose prompts are prefilled a window a chunk by
    ``_prefill_window``."""
    shape = dict(vocab_size=40, hidden_size=64, intermediate_size=96,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=4, rms_norm_eps=1e-5,
                 rope_theta=100000, window_size=32, chunk_size=4,
                 num_pred_heads=3, norm_add_unit_offset=True,
                 init_std=0.05)
    cfg = builders_evabyte.serving_config(shape, max_seq=160,
                                          dtype=jnp.float32)
    weights = builders_evabyte.make_weights(shape, cfg, 1)
    cache_cfg = default_cache_config(cfg, num_blocks=80, kv_dtype="model")
    return ServingEngine(
        weights, cfg, cache_cfg,
        ladder=BucketLadder(batch=(2,), pages=(16,), chunks=(16, 32)),
        speculate_k=0, prefill_chunk=32, prefix_share=False, slo=None)


def test_a_pooled_chunk_splits_its_hand_off_as_the_others_do():
    def run():
        engine = pooled_engine()
        engine.submit(Request(rid="a", prompt=[1] * 70, max_new_tokens=3))
        while engine.queue or engine.active or engine.prefilling:
            engine.step()
        return [list(q.out_tokens) for q in engine.done]

    plain = run()
    tracer = SpanTracer()
    set_tracer(tracer)
    try:
        assert run() == plain
    finally:
        set_tracer(None)
    spans = serve_spans(tracer.drain())
    # chunks of 32, 32 and 6: three dispatches, the last one's fetch
    assert chunk_parts(spans) == [["apex.serve.prefill.dispatch"]] * 2 + [
        PREFILL_PARTS]
    assert {s.depth for s in spans if s.name in PREFILL_PARTS} == {2}


def test_a_speculative_tick_uses_the_same_names(tiny, profiled):
    model, params = tiny
    cfg = ServingModelConfig.from_model(model, prefill_flash=False,
                                        decode_attention="reference")
    draft = extract_serving_weights(params, cfg.num_layers)
    spans, result = recorded_run(tiny, speculate_k=2, draft_weights=draft,
                                 draft_cfg=cfg)
    spans = serve_spans(spans)
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
    recorded = [s for s in tracer.drain() if s.name.startswith("apex.test.")]
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
    events = host_events(str(tmp_path), "apex.test.")
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
    (event,) = host_events(str(tmp_path), "apex.test.")
    assert event[0] == "apex.test.on"
    assert event[3] == {"tag": "y", "rows": 2}


def hooked():
    return gc.callbacks.count(tracing._collection_span)


def test_the_collectors_span_is_hooked_only_while_something_records(
        tiny, tmp_path):
    engine = make_engine(tiny)
    engine.step()
    assert not recording() and hooked() == 0
    tracer = SpanTracer()
    set_tracer(tracer)
    gc.disable()              # no collection but the one forced below
    try:
        engine.step()
        engine.step()
        assert hooked() == 1  # once, however many steps
        gc.collect()
    finally:
        gc.enable()
        set_tracer(None)      # a new recorder starts unhooked
    assert hooked() == 0
    (collected,) = [s for s in tracer.drain() if s.name == "apex.host.gc"]
    assert collected.attrs == {"generation": 2}
    # a profiler session: hooked from the first step that sees it, and
    # unhooked by the first step after it
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.step()
        assert hooked() == 1
        gc.collect(0)
    finally:
        jax.profiler.stop_trace()
    assert hooked() == 1
    engine.step()
    assert hooked() == 0
    events = host_events(str(tmp_path), "apex.host.gc")
    assert len(events) >= 1
    assert {e[3]["generation"] for e in events} >= {0}
