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
from benchmarks.readers import engine_fact, idle, module_ms, op_ms, roofline
from benchmarks.rooflines import flash_attention, paged_decode
from benchmarks.trace import (DeviceTrace, Event, breakdown, busy_seconds,
                              make_trace, short_op_name)

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
    ("gpt2-345m.serve-chat", True), ("gpt2-345m.serve-chat-sat", False)])
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


def hand_trace():
    """Two train steps of 10 ms (8 + 1 ms of ops, the attention op 4 ms
    of each), then a 6 ms decode tick (kernel 3 ms) and a 2 ms prefill,
    under host spans that cover 0-40 ms."""
    ms = 1e-3
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
    return make_trace([DeviceTrace(modules, ops)], spans)


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
    facts = {"attention_shapes": dict(batch=2, seq=64, heads=2,
                                      head_dim=64, layers=2, causal=False),
             "decode_shapes": dict(live_pages=10, live_tokens=70, rows=4,
                                   block_size=8, heads=2, head_dim=64,
                                   layers=2),
             "queue_wait_p90_ms": 3.0, "ttft_p90_ms": 200.0}
    got = bench_run.layer_metrics(BENCH, cell, hand_trace(), facts, PEAKS)
    assert set(got) == declared("per_layer", cell)
    assert all(v["value"] > 0 for v in got.values())


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
