"""The benchmark's harness on the CPU: every cell's code path end to end
at tiny sizes (kernels interpreted), the contract of the result line
and of ``BENCHMARK.json``, resolution of every name to its file, and
each reader and count function against a hand-made case.

Nothing here is a measurement: a number from these runs is never a
device metric.  No topology is described and the TPU library is not
touched.
"""
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import run as bench_run
from benchmarks import stats, traffic
from benchmarks.kinds import serve_open_loop
from benchmarks.readers import (engine_fact, fact_ratio, idle, module_ms,
                                op_ms, roofline, step_mfu)
from benchmarks.rooflines import flash_attention, paged_decode
from benchmarks.trace import (DeviceTrace, Event, breakdown, busy_seconds,
                              load_trace, make_trace, op_scopes, scope_ms,
                              short_op_name, short_scope)

ROOT = bench_run.ROOT
BENCH = bench_run.load_json(ROOT, "BENCHMARK.json")
PEAKS = bench_run.load_json(bench_run.HERE, "peaks.json")["TPU v5 lite"]
CELLS = [w["name"] for w in BENCH["workloads"]]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# --- tiny sizes (d = 64, so the E-layout flash path is taken) -----------------
GPT = {"n_layer": 2, "n_embd": 128, "n_head": 2, "n_inner": 512,
       "n_positions": 128, "vocab_size": 500,
       "assumed": {"padded_vocab_size": 512}}
BERT = {"num_hidden_layers": 2, "hidden_size": 128,
        "num_attention_heads": 2, "intermediate_size": 512,
        "vocab_size": 500, "assumed": {"padded_vocab_size": 512}}
TRAIN = {"batch": 2, "sequence": 64, "trace_steps": 2}
SERVE = {"rate_per_s": 6.0, "lead_in_s": 0.5, "trace_seconds": 0.5,
         "prompt_tokens": {"median": 12, "min": 4, "max": 40},
         "output_tokens": {"median": 6, "min": 2, "max": 12},
         "max_total_tokens": 64,
         "engine": {"block_size": 8, "page_rungs": [8], "batch_rungs": [4],
                    "num_blocks": 33}}
TINY = {
    "gpt2-345m.train": {"config": GPT, "traffic": TRAIN},
    "bert-large.train": {"config": BERT, "traffic": TRAIN},
    "gpt2-345m.serve-chat": {"config": GPT, "traffic": SERVE},
    "gpt2-345m.serve-chat-sat": {"config": GPT, "traffic": SERVE},
}


def declared(group, cell):
    return {m["name"] for m in bench_run.metrics_of(BENCH, group, cell)}


# --- BENCHMARK.json ------------------------------------------------------------

def test_benchmark_json_keeps_the_contracts_names_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32
    names = []
    for group, keys in (
            ("configs", {"name", "source", "file", "reduced", "why"}),
            ("workloads", {"name", "config", "traffic", "chips", "why"}),
            ("end_to_end", {"name", "unit", "better", "bound", "source"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"})):
        for entry in BENCH[group]:
            assert set(entry) - {"workloads"} == keys, entry
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group if group in ("configs", "workloads")
                          else "metric", entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names)), "a name appears twice"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in BENCH["end_to_end"])
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_every_name_resolves_to_a_file_of_its_own():
    """What a later PR relies on: a cell is found by name alone."""
    used_configs = set()
    for w in BENCH["workloads"]:
        cell, config, mix = bench_run.find_cell(BENCH, w["name"])
        used_configs.add(w["config"])
        assert callable(bench_run.resolve(config["builder"]))
        assert config["source"] == next(
            c["source"] for c in BENCH["configs"]
            if c["name"] == w["config"])
        kind = importlib.import_module(f"benchmarks.kinds.{mix['kind']}")
        assert callable(kind.run)
        assert len(declared("end_to_end", w["name"])) >= 2
        assert declared("per_layer", w["name"])
    assert used_configs == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith(tuple(p + "/" for p in BENCH["paths"]))
               for f in files)
    for m in BENCH["per_layer"]:
        spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                                   m["name"] + ".json")
        assert callable(bench_run.resolve(spec["reader"]))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        for cell in m.get("workloads", CELLS):
            assert m["moves"] in declared("end_to_end", cell), \
                f"{m['name']} moves {m['moves']}, which {cell} does not " \
                f"report"


def test_peaks_table_holds_the_v5e_row_with_its_source():
    assert PEAKS["bf16_flops_per_s"] == 197e12
    assert PEAKS["int8_ops_per_s"] == 393e12
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    assert PEAKS["hbm_bytes"] == 16e9
    assert PEAKS["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in PEAKS["source"]


# --- each cell, end to end, tiny ---------------------------------------------------

@pytest.mark.parametrize("cell,trace", [
    ("gpt2-345m.train", False), ("bert-large.train", True),
    ("gpt2-345m.serve-chat", True), ("gpt2-345m.serve-chat-sat", False),
    ("gpt2-345m.serve-chat-sat", True)])
def test_cell_runs_end_to_end_at_tiny_sizes(cell, trace, capsys):
    line = bench_run.run_cell(cell, 3000000019, 1.0, trace,
                              overrides=TINY[cell], require_tpu=False)
    out = capsys.readouterr().out
    assert "compiles_in_window=0" in out
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == keys | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    json.loads(json.dumps(line))
    if trace:
        # no device plane on the CPU: the readers find nothing, return
        # nothing, and no CPU number takes a device metric's name
        assert set(line["metrics"]) <= declared("per_layer", cell)
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == declared("end_to_end", cell)
        assert all(m["value"] > 0 for m in line["metrics"].values())
    if "serve" in cell:
        assert "lateness_p50_ms=" in out and "requests_due=" in out
        due = int(re.search(r"requests_due=(\d+)", out).group(1))
        assert line["attempted"] == due == 6
    if "serve" in cell and trace:
        # counts of the engine's own state: a CPU run gives them rightly
        grid = json.loads(re.search(r"decode_grid=(\{.*?\})", out).group(1))
        assert 0 < grid["ticks"] <= grid["rows"] <= grid["grid_rows"]
        assert grid["grid_rows"] == 4 * grid["ticks"]       # one rung: 4
        assert 0 < grid["live_pages"] <= grid["grid_pages"]
        assert grid["grid_pages"] == 8 * grid["grid_rows"]  # one rung: 8
        fills = {name: m["value"] for name, m in line["metrics"].items()
                 if "_fill_pct." in name}
        assert set(fills) == {name for name in declared("per_layer", cell)
                              if "_fill_pct." in name}
        assert all(0 < v <= 100 for v in fills.values())
        suffix = cell.rsplit("-", 1)[-1]                    # chat or sat
        assert fills[f"page_fill_pct.{suffix}"] == pytest.approx(
            100 * grid["live_pages"] / grid["grid_pages"])


def test_a_request_the_engine_refuses_counts_as_failed(capsys):
    tight = {"config": GPT, "traffic": {
        **SERVE, "engine": {**SERVE["engine"], "page_rungs": [2],
                            "num_blocks": 9}}}
    line = bench_run.run_cell("gpt2-345m.serve-chat", 5, 1.0, False,
                              overrides=tight, require_tpu=False)
    capsys.readouterr()
    assert 0 < line["failed"] <= line["attempted"]
    assert line["correct"] is False


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "{" not in done.stdout
    assert "no TPU" in done.stderr


# --- readers on hand-made events ---------------------------------------------------

ATTN = ("%self_attention.7 = bf16[2,64,384]{2,1,0} custom-call("
        "bf16[2,64,384]{2,1,0} %x)")
DECODE = ("%step.3 = bf16[4,1,1,128]{3,2,1,0} custom-call(s32[4,8]{1,0} "
          "%tables)")
PREFILL = "%step.9 = bf16[1,64,128]{2,1,0} custom-call(bf16[1,64,128] %q)"
FUSION = "%fusion.2 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop"


def events(rows):
    """``[[name, start_ms, dur_ms(, scope)], ...]``, the form a
    metric's ``example`` gives its events in."""
    return [Event(r[0], r[1] * 1e-3, r[2] * 1e-3, *r[3:]) for r in rows]


def hand_trace(example=None):
    """Two train steps of 10 ms (8 + 1 ms of ops, the attention op 4 ms
    of each), then a 6 ms decode tick (kernel 3 ms) and a 2 ms prefill,
    under host spans that cover 0-40 ms; with ``example`` (the key of a
    ``layer_metrics`` file), its events besides."""
    ms = 1e-3
    example = example or {}
    modules = [Event("jit__step(1)", 0 * ms, 10 * ms),
               Event("jit__step(1)", 12 * ms, 10 * ms),
               Event("jit_step(2)", 24 * ms, 6 * ms),
               Event("jit_step(3)", 32 * ms, 2 * ms)]
    ops = [Event(ATTN, 0 * ms, 4 * ms), Event(FUSION, 3 * ms, 5 * ms),
           Event(FUSION, 9 * ms, 1 * ms),
           Event(ATTN, 12 * ms, 4 * ms), Event(FUSION, 15 * ms, 5 * ms),
           Event(FUSION, 21 * ms, 1 * ms),
           Event(DECODE, 24 * ms, 3 * ms), Event(FUSION, 27 * ms, 3 * ms),
           Event(PREFILL, 32 * ms, 2 * ms)]
    spans = [Event("bench.step", 0.0, 1 * ms),
             Event("bench.loss_read", 1 * ms, 21 * ms),
             Event("bench.engine_step", 22.5 * ms, 17.5 * ms)]
    return make_trace(
        [DeviceTrace(modules + events(example.get("modules", [])),
                     ops + events(example.get("ops", [])))],
        spans + events(example.get("spans", [])),
        events(example.get("program_spans", [])))


# what the kinds would have recorded beside the hand trace
HAND_FACTS = {
    "attention_shapes": dict(batch=2, seq=64, heads=2, head_dim=64,
                             layers=2, causal=False),
    "decode_shapes": dict(live_pages=10, live_tokens=70, rows=4,
                          block_size=8, heads=2, head_dim=64, layers=2),
    "queue_wait_p90_ms": 3.0, "ttft_p90_ms": 200.0}

# PR 24's metrics carry no example: the shared hand trace and facts are
# theirs, and each has to go on reading them
NO_EXAMPLE = {
    "device_idle_pct.train", "step_device_ms.train",
    "attn_kernel_ms.train", "attn_roofline.train", "ttft_p90_ms.chat",
    "queue_wait_p90_ms.chat", "decode_tick_ms.chat",
    "decode_kernel_ms.chat", "decode_hbm_roofline.chat",
    "device_idle_pct.chat", "decode_tick_ms.sat", "device_idle_pct.sat"}


def read_declared(spec):
    """One per-layer metric through its own reader, on its own: over the
    shared hand trace and facts plus what its ``example`` adds.  The
    reading has to be there, positive and, where the example says what
    it comes to, that."""
    example = spec.get("example", {})
    value = bench_run.resolve(spec["reader"])(
        hand_trace(example), {**HAND_FACTS, **example.get("facts", {})},
        spec["params"], PEAKS)
    assert value is not None and value > 0, \
        f"{spec['name']} reads nothing: give layer_metrics/" \
        f"{spec['name']}.json an `example` that it reads"
    if "value" in example:
        assert value == pytest.approx(example["value"], rel=1e-6), \
            spec["name"]
    return value


def spec_params(name):
    return bench_run.load_json(bench_run.HERE, "layer_metrics",
                               name + ".json")["params"]


def test_idle_reader_takes_the_union_of_overlapping_intervals():
    t = hand_trace()
    # busy: [0,8] [9,10] [12,20] [21,22] [24,30] [32,34] = 26 of 40 ms
    assert busy_seconds(t) == pytest.approx(26e-3)
    assert idle.read(t, {}, {}, PEAKS) == pytest.approx(100 * 14 / 40)
    assert idle.read(make_trace([], []), {}, {}, PEAKS) is None


def test_module_and_op_readers_divide_by_the_programs_runs():
    t = hand_trace()
    assert module_ms.read(t, {}, spec_params("step_device_ms.train"),
                          PEAKS) == pytest.approx(10.0)
    assert op_ms.read(t, {}, spec_params("attn_kernel_ms.train"),
                      PEAKS) == pytest.approx(4.0)
    # decode ticks are told from prefills by the op they hold
    assert module_ms.read(t, {}, spec_params("decode_tick_ms.chat"),
                          PEAKS) == pytest.approx(6.0)
    assert op_ms.read(t, {}, spec_params("decode_kernel_ms.chat"),
                      PEAKS) == pytest.approx(3.0)
    assert module_ms.read(t, {}, {"module_pattern": "^jit_absent"},
                          PEAKS) is None


def test_roofline_reader_against_a_hand_count():
    t = hand_trace()
    shapes = dict(batch=2, seq=64, heads=2, head_dim=64, layers=2,
                  causal=True)
    facts = {"attention_shapes": shapes}
    flops, nbytes = flash_attention.train_step(**shapes)
    least = max(flops / 197e12, nbytes / 819e9)
    got = roofline.read(t, facts, spec_params("attn_roofline.train"),
                        PEAKS)
    assert got == pytest.approx(100 * 2 * least / 8e-3)   # 2 runs, 8 ms
    assert facts["roofline_bound"]["attention_shapes"] == "bytes"
    decode = dict(live_pages=10, live_tokens=70, rows=4, block_size=8,
                  heads=2, head_dim=64, layers=2)
    got = roofline.read(t, {"decode_shapes": decode},
                        spec_params("decode_hbm_roofline.chat"), PEAKS)
    _, nbytes = paged_decode.ticks(**decode)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 3e-3)
    assert roofline.read(t, {}, spec_params("attn_roofline.train"),
                         PEAKS) is None
    assert engine_fact.read(None, {"queue_wait_p90_ms": 7.5},
                            {"fact": "queue_wait_p90_ms"}, PEAKS) == 7.5


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_returns_every_layer_metric_the_cell_declares(cell):
    for name in sorted(declared("per_layer", cell)):
        spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                                   name + ".json")
        assert "example" not in spec or name not in NO_EXAMPLE
        read_declared(spec)
    # and all at once through the harness, as a traced run reads them
    examples = [bench_run.load_json(bench_run.HERE, "layer_metrics",
                                    name + ".json").get("example", {})
                for name in sorted(declared("per_layer", cell))]
    facts = dict(HAND_FACTS)
    for example in examples:
        facts.update(example.get("facts", {}))
    merged = {key: [row for example in examples
                    for row in example.get(key, [])]
              for key in ("modules", "ops", "spans", "program_spans")}
    got = bench_run.layer_metrics(BENCH, cell, hand_trace(merged), facts,
                                  PEAKS)
    assert set(got) == declared("per_layer", cell)
    assert all(v["value"] > 0 for v in got.values())


def test_a_metric_with_an_example_reads_its_value_and_one_without_fails():
    """What a later PR relies on: its metric's file brings the events
    the metric reads, and no file that is here is edited for it."""
    spec = {"name": "optimizer_ms.train",
            "reader": "benchmarks.readers.op_ms:read",
            "params": {"module_pattern": "^jit__step\\(",
                       "scope_pattern": "/apex\\.optimizer/"}}
    with pytest.raises(AssertionError, match="reads nothing"):
        read_declared(spec)
    sweep = ["%fusion.9 = bf16[4096]{0} fusion(bf16[4096]{0} %p)", 8.5,
             0.25, "jit(_step)/apex.optimizer/mul"]
    spec["example"] = {"ops": [sweep], "value": 0.125}    # over two runs
    assert read_declared(spec) == pytest.approx(0.125)
    spec["example"]["value"] = 0.25
    with pytest.raises(AssertionError):
        read_declared(spec)
    # an example's facts stand beside the shared ones
    spec = {"name": "fill", "reader": "benchmarks.readers.fact_ratio:read",
            "params": {"fact": "grid", "num": "live", "den": "slots"},
            "example": {"facts": {"grid": {"live": 3, "slots": 12}},
                        "value": 25.0}}
    assert read_declared(spec) == 25.0


def test_breakdown_names_ops_and_idle_gaps():
    assert short_op_name(ATTN) == "self_attention custom-call bf16[2,64,384]"
    assert short_op_name(FUSION) == "fusion f32[8,8]"
    b = breakdown(hand_trace())
    assert b["device_ops"][0][0] == "fusion f32[8,8]"
    assert b["device_ops"][0][1] == pytest.approx(15e-3)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) == 5
    # the longest gap, 34-40 ms, fell while the host was in engine_step
    assert b["idle_gaps"][0][0] == "bench.engine_step"
    assert b["idle_gaps"][0][1] == pytest.approx(6e-3)
    assert ["bench.loss_read", pytest.approx(2e-3)] in b["idle_gaps"]


def test_attention_pattern_reads_the_kernels_under_either_name():
    """Today the kernels are named after their flax scope
    (``%self_attention.N``); a PR that gives the ``pallas_call`` a
    ``name=`` may call them ``flash_attention_*``.  ``%flash_fwd.1``
    (the name such a call gets from ``name="flash_fwd"``) reads
    nothing."""
    renamed = ["%flash_attention_bwd.3 = bf16[2,64,384]{2,1,0} "
               "custom-call(bf16[2,64,384]{2,1,0} %x)", 8.25, 0.5]
    unknown = ["%flash_fwd.1 = bf16[2,64,384]{2,1,0} custom-call("
               "bf16[2,64,384]{2,1,0} %x)", 8.75, 0.125]
    t = hand_trace({"ops": [renamed, unknown]})
    assert op_ms.read(t, {}, spec_params("attn_kernel_ms.train"),
                      PEAKS) == pytest.approx(4.25)         # (4+4+.5)/2
    assert spec_params("attn_roofline.train")["op_pattern"] == \
        spec_params("attn_kernel_ms.train")["op_pattern"]


def test_fact_ratio_reader_on_hand_sums():
    params = {"fact": "decode_grid", "num": "rows", "den": "grid_rows"}
    grid = {"ticks": 3, "rows": 9, "grid_rows": 12, "live_pages": 20,
            "grid_pages": 96}
    assert fact_ratio.read(None, {"decode_grid": grid}, params,
                           PEAKS) == pytest.approx(75.0)
    assert fact_ratio.read(None, {"decode_grid": grid},
                           spec_params("page_fill_pct.chat"),
                           PEAKS) == pytest.approx(100 * 20 / 96)
    assert fact_ratio.read(None, {}, params, PEAKS) is None
    assert fact_ratio.read(None, {"decode_grid": None}, params,
                           PEAKS) is None
    assert fact_ratio.read(None, {"decode_grid": dict(grid, grid_rows=0)},
                           params, PEAKS) is None


def test_step_mfu_reader_takes_the_period_between_runs_in_the_window():
    """Two train steps start 12 ms apart (10 ms busy each): the share is
    of the period, idle gap and all, and of the device's clock."""
    params = spec_params("mfu_pct.train")
    flops = 0.012 * 197e12 / 4                               # a quarter
    assert step_mfu.read(hand_trace(), {"step_flops": flops}, params,
                         PEAKS) == pytest.approx(25.0)
    # a run that started before the window (the profiler's late first
    # step) and a stall after it do not enter
    early = hand_trace({"modules": [["jit__step(1)", -30, 10.0]],
                        "spans": [["bench.step", 60, 1.0]]})
    assert early.window[0] == 0.0
    assert step_mfu.read(early, {"step_flops": flops}, params,
                         PEAKS) == pytest.approx(25.0)
    third = hand_trace({"modules": [["jit__step(1)", 36, 10.0]]})
    assert step_mfu.read(third, {"step_flops": flops}, params,
                         PEAKS) == pytest.approx(100 * 12 / 18 / 4)
    assert step_mfu.read(hand_trace(), {}, params, PEAKS) is None
    assert step_mfu.read(None, {"step_flops": flops}, params, PEAKS) is None
    one = make_trace([DeviceTrace([Event("jit__step(1)", 0.0, 0.01)], [])],
                     [])
    assert step_mfu.read(one, {"step_flops": flops}, params, PEAKS) is None


def test_decode_grid_is_held_to_the_ticks_the_device_trace_holds():
    """The engine's ``decode_step`` events give the rows and rungs; the
    trace's decode calls (output ``bf16[bb,...]``, block table
    ``s32[bb,pb]``) have to say the same."""
    second = [["jit_step(2)", 60, 6.0]], [
        [DECODE.replace("step.3", "step.4"), 60, 1.0],
        [DECODE.replace("step.3", "step.5"), 62, 1.0]]    # two layers
    t = hand_trace({"modules": second[0], "ops": second[1]})
    assert serve_open_loop.traced_grid(t) == dict(
        traced_ticks=2, traced_grid_rows=8, traced_grid_pages=64)
    log = serve_open_loop.TickLog()
    log.event("serving", "admit", value=1, rid="a")
    log.event("serving", "decode_step", value=6.0, step=1, batch=3,
              batch_bucket=4, pages_bucket=8)
    assert log.ticks == [dict(step=1, batch=3, batch_bucket=4,
                              pages_bucket=8)]
    grid = dict(ticks=2, rows=5, grid_rows=8, live_pages=9, grid_pages=64)
    faults = []
    serve_open_loop.hold_grid_to_trace(faults, grid, t)
    assert faults == []
    for key in ("ticks", "grid_rows", "grid_pages"):
        faults = []
        serve_open_loop.hold_grid_to_trace(
            faults, dict(grid, **{key: grid[key] + 1}), t)
        assert len(faults) == 1 and key in faults[0]
    faults = []
    serve_open_loop.hold_grid_to_trace(faults, None, t)
    assert len(faults) == 3
    # a decode call that does not show its block table: rows alone
    bare = hand_trace({"modules": second[0], "ops": [
        ["%step.4 = bf16[4,1,1,128]{3,2,1,0} custom-call(%tables)", 60,
         1.0]]})
    assert serve_open_loop.traced_grid(bare)["traced_grid_pages"] is None
    faults = []
    serve_open_loop.hold_grid_to_trace(faults, dict(grid, grid_pages=1),
                                       bare)
    assert faults == []
    assert serve_open_loop.traced_grid(make_trace([], [])) == dict(
        traced_ticks=0, traced_grid_rows=0, traced_grid_pages=0)


def test_cache_layout_pattern_counts_cache_shaped_ops_alone():
    """Anchored on the cache's trailing dims: a fusion that makes the
    decode kernel's 4-dim ``q`` is not cache layout."""
    spec = bench_run.load_json(bench_run.HERE, "layer_metrics",
                               "cache_layout_ms.chat.json")
    pattern = spec["params"]["op_pattern"]
    for hlo in ("%copy.203 = bf16[24,2049,8,16,128]{4,3,2,1,0:T(8,128)(2,1)}"
                " copy(bf16[24,2049,8,16,128]{4,2,3,1,0} %fusion.49)",
                "%slice.290 = bf16[1,2049,8,16,128]{4,2,3,1,0:T(8,128)(2,1)"
                "S(1)} slice(bf16[24,2049,8,16,128]{4,2,3,1,0} %fusion.47)",
                "%copy_bitcast_fusion.2 = bf16[2049,8,16,128]{3,2,1,0} "
                "fusion(bf16[1,2049,8,16,128]{4,2,3,1,0} %slice.288)"):
        assert re.search(pattern, hlo), hlo
    for hlo in ("%fusion.77 = bf16[32,8,1,128]{3,2,1,0} fusion(bf16[32,16,64]"
                "{2,1,0} %q), kind=kLoop",
                "%step.116 = bf16[32,8,1,128]{3,2,1,0} custom-call(s32[32,64]"
                "{1,0} %t, bf16[2049,8,16,128]{3,2,1,0} %copy_bitcast)",
                "%fusion.5 = bf16[32,1024]{1,0} fusion(bf16[2049,8,16,128]"
                "{3,2,1,0} %x), kind=kLoop"):
        assert not re.search(pattern, hlo), hlo


def test_scope_pattern_is_anded_with_the_op_pattern():
    inside = "jit(_step)/apex.optimizer/mul"
    head = "jit(_step)/transpose(jvp(GPT))/GPT/transformer/layer_3/mlp/dot"
    t = hand_trace({"ops": [
        [FUSION.replace("fusion.2", "fusion.5"), 8.0, 0.5, inside],
        [FUSION.replace("fusion.2", "fusion.6"), 20.0, 0.25, inside],
        [ATTN.replace(".7", ".8"), 20.25, 0.125, inside],
        [FUSION.replace("fusion.2", "fusion.7"), 20.5, 0.25, head]]})
    params = {"module_pattern": "^jit__step\\(", "op_pattern": " fusion\\(",
              "scope_pattern": "/apex\\.optimizer/"}
    assert op_ms.read(t, {}, params, PEAKS) == pytest.approx(0.375)
    # no op_pattern: every op of the scope
    del params["op_pattern"]
    assert op_ms.read(t, {}, params, PEAKS) == pytest.approx(0.4375)
    # absent, the readers behave as before
    assert op_ms.read(t, {}, {"module_pattern": "^jit__step\\(",
                              "op_pattern": " fusion\\("},
                      PEAKS) == pytest.approx((12 + 1.0) / 2)
    # module_ms: runs that hold an op of the pattern and of the scope
    assert module_ms.read(t, {}, {"module_pattern": "^jit_",
                                  "contains_op": " custom-call\\(",
                                  "scope_pattern": "apex"},
                          PEAKS) == pytest.approx(10.0)
    shapes = dict(batch=2, seq=64, heads=2, head_dim=64, layers=2,
                  causal=True)
    scoped = dict(spec_params("attn_roofline.train"),
                  scope_pattern="apex")
    got = roofline.read(t, {"attention_shapes": shapes}, scoped, PEAKS)
    flops, nbytes = flash_attention.train_step(**shapes)
    assert got == pytest.approx(
        100 * 2 * max(flops / 197e12, nbytes / 819e9) / 0.125e-3)


def test_scope_ms_adds_every_layer_up_under_one_name():
    assert short_scope("jit(_step)/jvp(GPT)/GPT.h/transformer/layer_7/mlp/"
                       "dense_h_to_4h/dot_general") == \
        "jvp(GPT)/GPT.h/transformer/layer_N/mlp/dense_h_to_4h"
    assert short_scope("jit(step)/pallas_call") == "pallas_call"
    assert short_scope("cache.k") == "cache.k"
    assert short_scope("") == "(none)"
    mlp = "jit(_step)/jvp(GPT)/transformer/layer_%d/mlp/dot_general"
    t = hand_trace({"ops": [
        [FUSION.replace("fusion.2", "fusion.5"), 5.0, 2.0, mlp % 0],
        [FUSION.replace("fusion.2", "fusion.6"), 16.0, 1.0, mlp % 11]]})
    got = scope_ms(t)
    # per run of the busiest program (jit__step: two runs)
    assert got[0] == ("(none)", pytest.approx(28.0 / 2))
    assert got[1] == ("jvp(GPT)/transformer/layer_N/mlp",
                      pytest.approx(3.0 / 2))
    assert scope_ms(t, op="fusion f32[8,8]")[0] == \
        ("(none)", pytest.approx(15.0 / 2))
    assert scope_ms(make_trace([], [])) == []


# --- the trace file itself ---------------------------------------------------------

def _message(*fields):
    """A protobuf message from ``(number, int | bytes)`` fields (ints
    under 128 only: one-byte varints)."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += bytes([number << 3, value])
        else:
            assert len(value) < 128 * 128
            size = [len(value)] if len(value) < 128 \
                else [len(value) & 0x7F | 0x80, len(value) >> 7]
            out += bytes([number << 3 | 2, *size]) + value
    return out


def test_op_scopes_reads_the_event_metadata_of_device_planes(tmp_path):
    def plane(name, ops):
        stat_names = {1: b"hlo_category", 2: b"tf_op"}
        return _message(
            (2, name),
            *[(5, _message((1, k), (2, _message((1, k), (2, v)))))
              for k, v in stat_names.items()],
            *[(4, _message((1, i), (2, _message(
                (1, i), (2, hlo),
                (5, _message((1, 1), (5, b"fusion"))),
                *([(5, _message((1, 2), (5, scope)))] if scope else [])))))
              for i, (hlo, scope) in enumerate(ops, 1)])
    long = ("%fusion.9 = bf16[4096]{0} fusion(" + "x" * 200 + ")").encode()
    keyed_only = _message((1, 77))      # map entries with no value
    space = _message(
        (1, plane(b"/device:TPU:0", [
            (FUSION.encode(), b"jit(_step)/apex.optimizer/mul:"),
            (long, b"jit(_step)/reduce_sum:"),
            (ATTN.encode(), b"")]) + _message((4, keyed_only),
                                              (5, keyed_only))),
        (1, plane(b"/host:CPU", [(b"bench.step", b"not/an/op:")])))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(space)
    assert op_scopes(str(path)) == {
        FUSION: "jit(_step)/apex.optimizer/mul",
        long.decode(): "jit(_step)/reduce_sum"}


def test_load_trace_keeps_the_programs_spans_apart_from_the_window(tmp_path):
    """A CPU trace made here: an ``apex.*`` annotation (the program's)
    nested in a ``bench.*`` one (the benchmark's)."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.probe"):
        with jax.profiler.TraceAnnotation("apex.probe"):
            jnp.ones((8, 8)).sum().block_until_ready()
        with jax.profiler.TraceAnnotation("other.probe"):
            pass
    jax.profiler.stop_trace()
    t = load_trace(str(tmp_path))
    assert [s.name for s in t.spans] == ["bench.probe"]
    assert [s.name for s in t.program_spans] == ["apex.probe"]
    outer, inner = t.spans[0], t.program_spans[0]
    assert outer.start <= inner.start and inner.end <= outer.end
    assert inner.dur > 0
    assert t.window == (outer.start, outer.end)
    assert t.devices == []                    # no TPU plane on the CPU
    assert load_trace(str(tmp_path / "absent")) is None


def test_idle_gaps_are_named_by_the_innermost_span_of_either_prefix():
    fetch = ["apex.serve.decode.fetch", 34.0, 5.5]
    early = ["apex.serve.schedule", 22.5, 1.0]    # over before the gap
    b = breakdown(hand_trace({"program_spans": [fetch, early]}))
    assert b["idle_gaps"][0] == ["apex.serve.decode.fetch",
                                 pytest.approx(6e-3)]
    # 22-24 ms: its middle, 23 ms, lies in both; the inner one names it
    assert ["apex.serve.schedule", pytest.approx(2e-3)] in b["idle_gaps"]
    assert ["bench.loss_read", pytest.approx(2e-3)] in b["idle_gaps"]
    t = hand_trace({"program_spans": [fetch]})
    assert t.window == hand_trace().window
    assert idle.read(t, {}, {}, PEAKS) == pytest.approx(100 * 14 / 40)


# --- the count functions against hand counts ---------------------------------------

@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_counts_at_one_small_shape(causal):
    b, s, h, d, layers = 2, 8, 3, 4, 5
    pairs = 36 if causal else 64              # 8*9/2 or 8*8 per head
    one_matmul = 2 * b * h * pairs * d        # 2 flops a multiply-add
    tensor = b * s * h * d * 2                # bf16
    stats_ = b * h * s * 4
    want_flops = layers * 6 * one_matmul      # forward 2, backward 4
    want_bytes = layers * (4 * tensor + stats_ + 8 * tensor + stats_)
    assert flash_attention.train_step(
        batch=b, seq=s, heads=h, head_dim=d, layers=layers,
        causal=causal) == (want_flops, want_bytes)


def test_paged_decode_counts_at_one_small_shape():
    # 3 live pages of 4 positions, 9 filled; 2 rows; 2 heads of 8; 3 layers
    flops, nbytes = paged_decode.ticks(
        live_pages=3, live_tokens=9, rows=2, block_size=4, heads=2,
        head_dim=8, layers=3)
    assert nbytes == 3 * (2 * 3 * 4 * 16 * 2 + 2 * 2 * 16 * 2)
    assert flops == 3 * 4 * 9 * 16


# --- traffic and statistics ---------------------------------------------------------

def test_every_seed_offers_the_same_requests_at_the_same_instants():
    mix = bench_run.load_json(bench_run.HERE, "traffic",
                              "chat-0.8knee.json")
    a = traffic.open_loop_schedule(mix, 1, 20.0, 50257)
    b = traffic.open_loop_schedule(mix, 3000000019, 20.0, 50257)
    again = traffic.open_loop_schedule(mix, 1, 20.0, 50257)
    assert [(x.due_s, x.prompt) for x in a] == \
        [(x.due_s, x.prompt) for x in again]

    def shape(s):
        return [(x.rid, x.due_s, len(x.prompt), x.max_new_tokens)
                for x in s]

    assert shape(a) == shape(b)               # the file's schedule_seed
    assert [x.prompt for x in a] != [x.prompt for x in b]     # --seed
    other = traffic.open_loop_schedule(dict(mix, schedule_seed=1), 1,
                                       20.0, 50257)
    assert shape(other) != shape(a)
    assert sorted(len(x.prompt) for x in other) == \
        sorted(len(x.prompt) for x in a)      # same work, another order
    window = [x for x in a if x.rid.startswith("req")]
    assert len(window) == round(mix["rate_per_s"] * 20.0)
    assert all(0.0 <= x.due_s < 20.0 for x in window)
    assert all(-mix["lead_in_s"] <= x.due_s < 0.0
               for x in a if x.rid.startswith("lead"))
    assert all(len(x.prompt) + x.max_new_tokens <= 1024
               and 16 <= len(x.prompt) <= 768
               and 8 <= x.max_new_tokens <= 256 for x in a)
    assert max(t for x in a for t in x.prompt) < 50257
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)


def test_percentile_interpolates_between_ranks():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0]            # sorted: 1 3 5 7 9
    assert stats.percentile(xs, 50) == 5.0
    assert stats.percentile(xs, 90) == pytest.approx(8.2)   # rank 3.6
    assert stats.percentile(xs, 100) == 9.0
    assert stats.percentile([], 90) is None
